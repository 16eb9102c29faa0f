"""Paper Fig. 2 — kernel power profiles under PMT, stacked CPU + accel.

Runs the paper's benchmark set (SLEEP, FMA32, STREAM, GRIDDER, DEGRIDDER,
GEMM, JACOBI2D) instrumented with two stacked sensors, exactly like the
paper's stacked decorators: the *measured* host sensor (cpuutil) and the
*modeled* accelerator sensor (tpu — fed the kernel's own compiled cost
analysis).  Kernels run compiled on a TPU and in Pallas interpret mode
only on a CPU backend (the run prints which); the TPU energy numbers are
the analytical model evaluated on each kernel's real FLOPs/bytes (kind
labels make measured-vs-modeled explicit).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

import repro.core as pmt
from repro.core.backends.tpu import TpuCostModelSensor


def _cost(fn, *args):
    c = jax.jit(fn).lower(*args).compile().cost_analysis() or {}
    return float(c.get("flops", 0.0)), float(c.get("bytes accessed", 0.0))


def _run(name, fn, args, flops, bytes_, rows, repeats=3):
    """cpu watts: measured over the run.  tpu watts: the
    model evaluated at the kernel's TPU-projected duration (roofline max
    of compute and HBM time) — i.e. what the chip would draw actually
    executing this kernel, which is what reproduces Fig. 2's contrast
    between FLOP-bound, bandwidth-bound and idle kernels."""
    cpu = pmt.create("cpuutil")
    tpu = TpuCostModelSensor.create()
    s_cpu = cpu.read()
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    e_cpu = cpu.read()
    model = tpu.model
    t_tpu = max(flops / model.hw.peak_flops,
                bytes_ / model.hw.hbm_bw, 1e-9)
    w_tpu = model.step_watts(flops, bytes_, 0.0, t_tpu)
    j_tpu = model.step_joules(flops, bytes_, 0.0, t_tpu)
    rows.append((name, dt / repeats, pmt.watts(s_cpu, e_cpu), w_tpu,
                 j_tpu))


def main(csv=False):
    rows = []
    key = jax.random.PRNGKey(0)
    platform = jax.default_backend()
    interpret = platform == "cpu"
    print(f"# kernels: {'Pallas interpret mode' if interpret else 'compiled'}"
          f" on {platform}")

    # SLEEP — idle power floor
    cpu = pmt.create("cpuutil")
    tpu = TpuCostModelSensor.create()
    s0, t0r = cpu.read(), tpu.read()
    time.sleep(0.3)
    tpu.account(flops=0, hbm_bytes=0, ici_bytes=0.0, seconds=0.3)
    rows.append(("SLEEP", 0.3, pmt.watts(s0, cpu.read()),
                 pmt.watts(t0r, tpu.read()),
                 tpu.model.static_joules(0.3)))

    from repro.kernels.fma32.ops import fma32
    x = jax.random.normal(key, (1024, 512), jnp.float32)
    # 1024 chained FMAs/element -> 512 FLOP/byte, past the v5e ridge
    # point (240), so the modeled kernel is compute-bound like the paper's
    fn = lambda a: fma32(a, iters=1024, interpret=interpret)
    f, b = 2.0 * x.size * 1024, 2.0 * x.size * 4
    _run("FMA32", fn, (x,), f, b, rows)

    from repro.kernels.stream.ops import stream_triad
    a = jax.random.normal(key, (4096, 512), jnp.float32)
    bb = jax.random.normal(key, (4096, 512), jnp.float32)
    fn = lambda p, q: stream_triad(p, q, interpret=interpret)
    f, by = 2.0 * a.size, 3.0 * a.size * 4
    _run("STREAM", fn, (a, bb), f, by, rows)

    from repro.kernels.gridder.ops import degridder, gridder
    P, S, V = 256, 4, 512
    lm = jax.random.uniform(key, (P, 2), minval=-0.5, maxval=0.5)
    uv = jax.random.uniform(key, (S, V, 2), minval=-2, maxval=2)
    vis = jax.random.normal(key, (S, V, 2), jnp.float32)
    f = 8.0 * S * V * P
    by = 4.0 * (S * V * 4 + S * P * 2) * 4
    _run("GRIDDER", lambda *z: gridder(*z, interpret=interpret), (lm, uv, vis),
         f, by, rows)
    sub = jax.random.normal(key, (S, P, 2), jnp.float32)
    _run("DEGRIDDER", lambda *z: degridder(*z, interpret=interpret),
         (lm, uv, sub), f, by, rows)

    from repro.kernels.gemm.ops import gemm
    m = jax.random.normal(key, (512, 512), jnp.float32)
    n = jax.random.normal(key, (512, 512), jnp.float32)
    f, by = 2.0 * 512 ** 3, 3.0 * 512 * 512 * 4
    _run("GEMM", lambda p, q: gemm(p, q, block_m=256, block_n=256,
                                   block_k=256, interpret=interpret), (m, n),
         f, by, rows)

    from repro.kernels.jacobi2d.ops import jacobi2d
    j = jax.random.normal(key, (1024, 512), jnp.float32)
    f, by = 5.0 * j.size, 2.0 * j.size * 4
    _run("JACOBI2D", lambda p: jacobi2d(p, interpret=interpret), (j,), f, by,
         rows)

    print("# Fig.2 — PMT stacked measurement: CPU (measured) + "
          "TPU (modeled)")
    print(f"{'kernel':10s} {'s/iter':>9s} {'cpu_W':>8s} {'tpu_W':>8s} "
          f"{'tpu_J/iter':>11s}")
    for name, dt, wc, wt, jt in rows:
        print(f"{name:10s} {dt:9.4f} {wc:8.2f} {wt:8.2f} {jt:11.4f}")
    if csv:
        for name, dt, wc, wt, jt in rows:
            print(f"fig2_{name.lower()},{dt*1e6:.1f},"
                  f"cpuW={wc:.2f};tpuW={wt:.2f}")
    return rows


if __name__ == "__main__":
    main()
