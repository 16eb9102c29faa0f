"""Reproduce the *shape* of the paper's Fig. 2 as a dump-mode timeline:
idle -> compute-bound (FMA) -> bandwidth-bound (STREAM) -> GEMM, with the
stacked CPU (measured) + TPU (modeled) sensors, then render the power
trace as ASCII.

Run: PYTHONPATH=src python examples/power_timeline.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as pmt
from repro.core.backends.tpu import TpuCostModelSensor
from repro.kernels.fma32.ops import fma32
from repro.kernels.gemm.ops import gemm
from repro.kernels.stream.ops import stream_triad


def main():
    cpu = pmt.create("cpuutil")
    tpu = TpuCostModelSensor.create()
    platform = jax.default_backend()
    interpret = platform == "cpu"
    print(f"kernels: {'Pallas interpret mode' if interpret else 'compiled'}"
          f" on {platform}")

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1024, 512), jnp.float32)
    a = jax.random.normal(key, (2048, 512), jnp.float32)
    b = jax.random.normal(key, (2048, 512), jnp.float32)
    m = jax.random.normal(key, (512, 512), jnp.float32)

    phases = []
    with cpu.dumping("/tmp/fig2_cpu.pmt", period_s=0.05), \
            tpu.dumping("/tmp/fig2_tpu.pmt", period_s=0.05):
        for name, fn, (fl, by) in [
            ("IDLE", lambda: time.sleep(0.6), (0, 0)),
            ("FMA32", lambda: jax.block_until_ready(
                fma32(x, iters=128, interpret=interpret)),
             (2.0 * x.size * 128, 2.0 * x.size * 4)),
            ("STREAM", lambda: jax.block_until_ready(
                stream_triad(a, b, interpret=interpret)),
             (2.0 * a.size, 3.0 * a.size * 4)),
            ("GEMM", lambda: jax.block_until_ready(
                gemm(m, m, interpret=interpret)),
             (2.0 * 512 ** 3, 3.0 * 512 * 512 * 4)),
        ]:
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            tpu.account(flops=fl, hbm_bytes=by, ici_bytes=0.0,
                        seconds=max(dt, 1e-3))
            phases.append((name, dt))
            time.sleep(0.3)

    for path, label in (("/tmp/fig2_cpu.pmt", "CPU (measured)"),
                        ("/tmp/fig2_tpu.pmt", "TPU (modeled)")):
        _, recs = pmt.read_dump(path)
        w = np.array([r.watts for r in recs])
        if not len(w):
            continue
        lo, hi = w.min(), max(w.max(), w.min() + 1e-3)
        bars = ((w - lo) / (hi - lo) * 7).astype(int)
        blocks = "▁▂▃▄▅▆▇█"
        print(f"{label:16s} [{lo:6.1f}W..{hi:6.1f}W] "
              + "".join(blocks[i] for i in bars))
    print("phases:", ", ".join(f"{n}({dt:.2f}s)" for n, dt in phases))
    print("timelines: /tmp/fig2_cpu.pmt /tmp/fig2_tpu.pmt")


if __name__ == "__main__":
    main()
