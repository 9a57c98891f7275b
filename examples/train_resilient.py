"""End-to-end driver: train the ~100M demo LM with full VELOC checkpointing,
kill it mid-run, and recover — all on CPU.

    PYTHONPATH=src python examples/train_resilient.py            # quick (~2 min)
    PYTHONPATH=src python examples/train_resilient.py --full     # few hundred steps

Internally this is ``repro.launch.train`` — the same driver the cluster
launcher uses — with the failure simulator armed.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.train import main

if __name__ == "__main__":
    full = "--full" in sys.argv
    steps = "300" if full else "60"
    args = ["--arch", "veloc-demo-100m", "--steps", steps,
            "--seq-len", "128", "--batch", "8",
            "--ckpt-every", "10", "--mode", "async", "--capture", "fused",
            "--phase-predictor", "ema",
            "--fail-at", "35" if not full else "150",
            "--scratch", "/tmp/veloc_resilient"]
    if not full:
        args += ["--smoke"] if os.environ.get("VELOC_SMOKE") else []
    losses = main(args).losses
    assert losses[-1] < losses[0], "loss should decrease"
    print("resilient training example OK")
