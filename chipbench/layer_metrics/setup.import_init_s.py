"""Seconds of set-up in the package's import and `hvd.init()`: the
``hvd_import`` and ``hvd_init`` spans of the program's ring
(chipbench/stall_spans.py)."""
from chipbench import stall_spans


def read(run):
    return stall_spans.setup_seconds(run, "import_init_s")
