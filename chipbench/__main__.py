import time

_T0 = time.perf_counter()   # before jax and the program are imported

import sys  # noqa: E402

from .harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=_T0))
