"""Train a reduced-config LM end-to-end on CPU with the full substrate:
sharded data pipeline, AdamW, atomic checkpointing, fault-tolerant loop
(including an injected mid-run failure + bit-exact resume).

    PYTHONPATH=src python examples/train_lm.py [arch]
"""
import shutil
import sys
import tempfile

from repro.launch import train
from repro.runtime.compile_cache import enable_compile_cache

enable_compile_cache()

arch = sys.argv[1] if len(sys.argv) > 1 else "mamba2-370m"
ckpt = tempfile.mkdtemp(prefix="repro_ckpt_")
try:
    log = train.main(["--arch", arch, "--smoke", "--steps", "40",
                      "--batch", "8", "--seq", "64", "--ckpt", ckpt,
                      "--save-every", "10"])
    losses = [m["loss"] for m in log]
    assert losses[-1] < losses[0], "loss did not improve"
    print(f"\nloss improved {losses[0]:.3f} -> {losses[-1]:.3f}; "
          f"checkpoints in {ckpt} (atomic, keep-last-3)")
finally:
    shutil.rmtree(ckpt, ignore_errors=True)
