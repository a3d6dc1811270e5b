"""Serve a small model with batched requests: greedy decode over a KV cache.

Run:  PYTHONPATH=src python examples/serve_decode.py --arch zamba2-7b
(any non-encoder arch id works; models are reduced-size for CPU)
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch import serve
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache()
    serve.main()


if __name__ == "__main__":
    main()
