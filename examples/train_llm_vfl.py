"""End-to-end driver: federated-train a ~100M-class LM with the distributed
VFL round (per-vehicle replicas on the data axis, VEDS-gated aggregation).

This is the big-model version of the paper's pipeline: vehicles = data-axis
groups of a jax mesh, model upload = the masked psum in fl/vfl.py.

  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/train_llm_vfl.py --rounds 50
(thin wrapper over repro.launch.train with a larger reduced config)
"""
import sys

from repro.launch.train import main as train_main


def main(argv=None):
    base = ["--arch", "qwen3-32b", "--rounds", "50", "--devices", "8",
            "--vehicles", "4", "--seq", "128", "--batch-per-vehicle", "8",
            "--lr", "0.5"]
    extra = sys.argv[1:] if argv is None else list(argv)
    return train_main(base + extra)


if __name__ == "__main__":
    sys.exit(main())
