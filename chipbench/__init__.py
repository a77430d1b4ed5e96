"""chipbench: the chip benchmark of horovod_tpu (see README.md here)."""
