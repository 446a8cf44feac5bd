"""Continuous-serving inference example: generation requests flow through
the resident engine + METG-batching frontend (`repro.core.serving`) —
bounded admission, dynamic batch sizing, per-request latency percentiles.

    PYTHONPATH=src python examples/serve_dwork.py
"""
from repro.launch.serve import main

main(["--arch", "deepseek-7b", "--reduced", "--requests", "6",
      "--prompt-len", "16", "--max-new", "4"])
