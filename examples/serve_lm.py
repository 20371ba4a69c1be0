"""Serve a reduced-config LM with SharedDB heartbeat cycles: batched
admission, one always-on compiled plan, bounded per-cycle work.

    PYTHONPATH=src python examples/serve_lm.py [arch]
"""
import sys

from repro.launch import serve
from repro.runtime.compile_cache import enable_compile_cache

enable_compile_cache()

arch = sys.argv[1] if len(sys.argv) > 1 else "recurrentgemma-2b"
serve.main(["--arch", arch, "--smoke", "--requests", "24",
            "--capacity", "8", "--max-seq", "96", "--prefill-len", "24",
            "--new-tokens", "12"])
