"""Pallas TPU kernel: dynamic-rate estimation over resampled tracks.

Computes vertical rate, ground speed, heading and turn rate with central
differences (paper §III.A step 3: "estimating dynamic rates (e.g.
vertical rate)"). Pure VPU stencil work: shifts + transcendentals, fused
in one pass over VMEM so each track is read once (the unfused jnp oracle
materializes ~10 intermediates in HBM).

Layout: channel-major (B, 3, M) so the track axis M sits in the 128-wide
lane dimension; shifts are lane rotations. Grid over B; each step holds a
(3, M) block and writes a (4, M) block — at M = 4096 that is 112 KB of
VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

M_PER_DEG = 111_111.0


def _make_central(M: int, cnt: jax.Array, dt: float):
    """Clamped-neighbor derivative: central inside [0, cnt), one-sided at
    both track ends. Lane rotations + select, no gathers."""
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, M), 1)
    last = cnt - 1
    denom = (jnp.minimum(idx + 1, jnp.maximum(last, 0))
             - jnp.maximum(idx - 1, 0))
    denom = jnp.maximum(denom, 1).astype(jnp.float32) * dt

    def central(x: jax.Array) -> jax.Array:
        # The wrapped lanes (x_l at 0, x_r at M-1) are replaced below.
        x_l = pltpu.roll(x, 1, 1)                          # x[i-1]
        x_r = pltpu.roll(x, M - 1, 1)                      # x[i+1]
        left = jnp.where(idx == 0, x, x_l)
        right = jnp.where(idx >= last, x, x_r)
        return (right - left) / denom

    return central, idx


def _atan2(y: jax.Array, x: jax.Array) -> jax.Array:
    """Elementwise arctan2 from VPU ops (the TPU kernel lowering has no
    atan).  Octant reduction to [0, 1], then Cephes' atanf range split
    and polynomial: ~1e-7 rad from jnp.arctan2."""
    ax, ay = jnp.abs(x), jnp.abs(y)
    hi = jnp.maximum(ax, ay)
    a = jnp.minimum(ax, ay) / jnp.where(hi == 0.0, 1.0, hi)   # [0, 1]
    big = a > 0.41421356                                      # tan(pi/8)
    r = jnp.where(big, (a - 1.0) / (a + 1.0), a)
    z = r * r
    p = ((((8.05374449538e-2 * z - 1.38776856032e-1) * z
           + 1.99777106478e-1) * z - 3.33329491539e-1) * z * r + r)
    p = jnp.where(big, p + 0.25 * jnp.pi, p)
    p = jnp.where(ay > ax, 0.5 * jnp.pi - p, p)
    p = jnp.where(x < 0.0, jnp.pi - p, p)
    return jnp.where(y < 0.0, -p, p)


def _kernel(count_ref, v_ref, out_ref, *, dt: float):
    # count (B,) in SMEM (scalar prefetch); blocks v (3, M), out (4, M).
    lat = v_ref[0:1, :]
    lon = v_ref[1:2, :]
    alt = v_ref[2:3, :]
    cnt = count_ref[pl.program_id(0)]
    M = lat.shape[1]
    central, idx = _make_central(M, cnt, dt)

    vrate = central(alt)
    dn = central(lat) * M_PER_DEG
    de = central(lon) * M_PER_DEG * jnp.cos(jnp.deg2rad(lat))
    gspeed = jnp.sqrt(dn * dn + de * de)
    heading = _atan2(de, dn)
    dh = central(heading) * dt
    dh = (dh + jnp.pi) % (2.0 * jnp.pi) - jnp.pi
    turn = dh / dt

    valid = idx < cnt
    for k, plane in enumerate((vrate, gspeed, heading, turn)):
        out_ref[k:k + 1, :] = jnp.where(valid, plane, 0.0)


@functools.partial(jax.jit, static_argnames=("dt", "interpret"))
def dynamic_rates_pallas(v: jax.Array, count: jax.Array, dt: float,
                         *, interpret: bool = True) -> jax.Array:
    """Pallas version of ref.dynamic_rates_ref.

    v (B, 3, M) f32, count (B,) i32 -> (B, 4, M) f32; M a multiple of
    128 (ops.py pads).
    """
    B, C, M = v.shape
    assert C == 3, v.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[pl.BlockSpec((None, 3, M), lambda b, cnt: (b, 0, 0))],
        out_specs=pl.BlockSpec((None, 4, M), lambda b, cnt: (b, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, dt=float(dt)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 4, M), jnp.float32),
        interpret=interpret,
    )(count.astype(jnp.int32), v.astype(jnp.float32))
