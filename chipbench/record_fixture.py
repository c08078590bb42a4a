"""Record the small device trace that ``tests/test_trace.py`` reads.

Runs three launches of a Pallas int8 matmul and of a plain XLA matmul, in
host spans inside a ``bench.window`` span, under the JAX profiler, and
copies the ``.xplane.pb`` to ``<out_dir>/fixture.xplane.pb`` (the test's
copy is ``chipbench/tests/fixture.xplane.pb``). It also prints every plane,
line and event name, which is how the reduction's names were read.

Usage:  python chipbench/record_fixture.py <out_dir>
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import time

import common  # noqa: F401  (paths and compile cache)


def main():
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    out = sys.argv[1]
    os.makedirs(out, exist_ok=True)
    xq = jnp.ones((256, 1024), jnp.int8)
    wq = jnp.ones((1024, 2048), jnp.int8)
    a = jnp.ones((512, 512), jnp.float32)
    mm = jax.jit(lambda u: u @ u)
    qmm = jax.jit(lambda x, w: ops.quant_matmul(x, w, jnp.float32(0.5),
                                                jnp.float32(0.25)))
    jax.block_until_ready((mm(a), qmm(xq, wq)))
    tdir = os.path.join(out, "trace")
    with jax.profiler.trace(tdir):
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(3):
                with jax.profiler.TraceAnnotation("bench.step", i=i):
                    jax.block_until_ready(qmm(xq, wq))
                    jax.block_until_ready(mm(a))
                with jax.profiler.TraceAnnotation("bench.host"):
                    time.sleep(0.002)
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    shutil.copy(path, os.path.join(out, "fixture.xplane.pb"))
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            names = {}
            for ev in line.events:
                names[ev.name] = names.get(ev.name, 0) + 1
            print(f"  line {line.name!r}: {len(names)} names")
            for n, c in list(names.items())[:40]:
                print(f"    {c:4d} {n[:160]!r}")


if __name__ == "__main__":
    main()
