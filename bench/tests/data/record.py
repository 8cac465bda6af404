"""Record the small TPU trace the reducer's test reads.

    python3 bench/tests/data/record.py     # on a machine with a TPU

A jitted chain of matmuls runs a few times inside a ``bench.window``
annotation, with host sleeps between calls so the device idles; the
profiler's ``.xplane.pb`` is copied to ``tpu_v5e_small.xplane.pb`` here.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

HERE = Path(__file__).resolve().parent


@jax.jit
def matmul_chain(x):
    for _ in range(4):
        x = jnp.tanh(x @ x)
    return x


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record.py needs a TPU")
    x = jnp.ones((512, 512), jnp.float32) / 512
    matmul_chain(x).block_until_ready()
    with tempfile.TemporaryDirectory(dir=HERE) as d:
        jax.profiler.start_trace(d)
        with TraceAnnotation("bench.window"):
            for _ in range(3):
                with TraceAnnotation("host.sleep"):
                    time.sleep(0.002)
                matmul_chain(x).block_until_ready()
        jax.profiler.stop_trace()
        src = sorted(Path(d).rglob("*.xplane.pb"))[-1]
        shutil.copy(src, HERE / "tpu_v5e_small.xplane.pb")
    print((HERE / "tpu_v5e_small.xplane.pb").stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
