"""Quickstart: progressive (SmartFreeze) training of a reduced llama3-8b on
CPU in under a minute — stages train, the pace controller freezes them, the
model grows. See examples/federated_cifar.py for the paper's FL testbed and
examples/serve_decode.py for serving.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.cache import use_compile_cache
from repro.launch.train import train


def main():
    use_compile_cache()
    out = train("llama3-8b", reduced=True, steps=16, batch=4, seq=64,
                num_pods=1, lr=5e-3)
    history = out["history"]
    print()
    for stage in sorted({h["stage"] for h in history}):
        ls = [h["loss"] for h in history if h["stage"] == stage]
        print(f"stage {stage}: loss {ls[0]:.3f} -> {ls[-1]:.3f} over {len(ls)} rounds")
        # each stage must improve its own objective (the output module is
        # re-initialized at stage boundaries, so cross-stage loss jumps are
        # expected — see the paper's Fig. 5 growth procedure)
        assert ls[-1] < ls[0] or len(ls) < 3, (stage, ls)


if __name__ == "__main__":
    main()
