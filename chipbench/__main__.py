import time

T0 = time.monotonic()  # set-up is timed from here, before JAX is imported

import sys  # noqa: E402

from chipbench.harness import main  # noqa: E402

sys.exit(main(t0=T0))
