"""Flagship end-to-end example: multi-tenant LLM serving over tiered memory.

A real (smoke-scale) transformer serves two tenants through the paged KV
cache; MaxMem samples the Quest page-access stream, runs its FMMR policy
every few steps, and migrates hot KV pages into the fast slots with the
Pallas page-move kernel. The LS tenant's pages win fast-tier residency.

    PYTHONPATH=src python examples/serve_tiered.py

Drop ``--smoke`` (``python -m repro.launch.serve``) to serve qwen2.5-3b at
its published widths on a TPU.
"""
from repro.launch import serve

if __name__ == "__main__":
    # the serving driver IS the example; keep one source of truth
    serve.main(["--smoke", "--new-tokens", "60", "--fast-pages", "6",
                "--slow-pages", "90", "--quest-pages", "2"])
