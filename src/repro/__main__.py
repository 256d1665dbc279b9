"""``python -m repro`` — the unified driver (DESIGN.md §13).

    python -m repro run examples/specs/quickstart_run.json
    python -m repro run --network scenario:powerlaw --scale 0.02 --eval recovery
    python -m repro run --bench              # registered-suite fast pass
    python -m repro solve|serve|scenario|bench ...   # deprecation shims

The sharded backend and the bench matrix's sharded cells need multiple
devices; on CPU hosts they are fabricated via XLA_FLAGS, which must be
set before ANY jax import (the device count locks at jax init).  argv is
peeked here because argparse runs after import, inside main().
"""

import os
import sys

_DEVICES = 8 if "--full" in sys.argv else 4
os.environ.setdefault(
    "XLA_FLAGS", f"--xla_force_host_platform_device_count={_DEVICES}"
)

from repro.launch.cli import main  # noqa: E402

if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
