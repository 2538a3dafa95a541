"""Pallas TPU kernel: piecewise-linear track resampling.

The workflow's hot loop interpolates raw, irregularly-sampled ADS-B/radar
observations onto a uniform time grid (paper §III.A step 3). On CPU/GPU
this is a searchsorted + gather. Neither maps well to the TPU: gathers
serialize on the VPU and searchsorted is branch-heavy.

TPU adaptation: reformulate interpolation as two masked matmuls on the
MXU. For output times t (M,) and input knots T (N,):

    cond[m, n] = 1 if t_m falls in segment [T_n, T_{n+1})          (M, N)
    WL = cond * (1 - w),  WR = cond * w,   w = (t - T_n)/(T_{n+1} - T_n)
    out = V @ WL^T + Vshift @ WR^T          -- V: (C, N) channel values

Both matmuls are MXU ops; cond/w are VPU elementwise. The O(M*N) FLOPs
are far cheaper than the memory stalls of a gather at these sizes
(N, M <= a few K), and the whole working set tiles cleanly into VMEM.

Block layout: grid (B, M/MB); per step we hold (1, N), (C, N), (1, MB)
blocks in VMEM and write a lane-dense (C, MB) output block — with
N = 1024, C = 3, MB = 256 that is ~30 KB, well under the ~16 MB VMEM
budget, leaving room for the (MB, N) mask intermediates (1 MB each).
The per-track counts ride in SMEM (scalar prefetch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(count_ref, t_in_ref, v_in_ref, t_out_ref, out_ref):
    # count (B,) in SMEM (scalar prefetch).  Blocks (row dim squeezed):
    # t_in (1, N), v_in (C, N), t_out (1, MB), out (C, MB).
    t = t_in_ref[...]                        # (1, N)
    v = v_in_ref[...]                        # (C, N)
    cnt = count_ref[pl.program_id(0)]        # scalar int32
    q = t_out_ref[...]                       # (1, MB)
    N = t.shape[1]
    MB = q.shape[1]

    last = cnt - 1
    n_iota = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
    # Clamp queries into the valid time range (constant extrapolation).
    t_last = jnp.sum(jnp.where(n_iota == last, t, 0.0), axis=1,
                     keepdims=True)                          # (1, 1)
    q = jnp.clip(q, t[:, 0:1], t_last)
    # Queries down the sublanes: the (8, MB) -> (MB, 8) transpose is a
    # native tile op, a (1, MB) one is not.
    qm = jnp.transpose(jnp.broadcast_to(q, (8, MB)))[:, 0:1]  # (MB, 1)

    # Segment n is valid for n in [0, last-1]; its interval [T_n, T_{n+1}).
    # t_next wraps at n = N-1, which no valid segment reaches (last < N).
    t_next = pltpu.roll(t, N - 1, 1)                         # t[n + 1]
    seg_valid = n_iota < last                                # (1, N)
    is_last_seg = n_iota == (last - 1)

    cond = (qm >= t) & ((qm < t_next) | (is_last_seg & (qm <= t_next)))
    cond = cond & seg_valid                                  # (MB, N)

    denom = jnp.where(t_next > t, t_next - t, 1.0)
    w = (qm - t) / denom                                     # (MB, N)
    # Select, not multiply: w is -inf against +inf knot padding, and
    # 0 * inf would put a NaN into every product of the matmul.
    wl = jnp.where(cond, 1.0 - w, 0.0)
    wr = jnp.where(cond, w, 0.0)

    v_shift = pltpu.roll(v, N - 1, 1)                        # v[:, n + 1]
    # MXU: (C, N) x (MB, N)^T twice, lane-dense (C, MB) result.  f32
    # operands at HIGHEST precision — a single bf16 pass would put a
    # latitude kilometres off.
    dims = (((1,), (1,)), ((), ()))
    hi = jax.lax.Precision.HIGHEST
    out = jax.lax.dot_general(v, wl, dims, precision=hi,
                              preferred_element_type=jnp.float32)
    out += jax.lax.dot_general(v_shift, wr, dims, precision=hi,
                               preferred_element_type=jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def track_interp_pallas(t_in: jax.Array, v_in: jax.Array, count: jax.Array,
                        t_out: jax.Array, *, block_m: int = 512,
                        interpret: bool = True) -> jax.Array:
    """Pallas version of ref.track_interp_ref, channel-major output.

    t_in (B, N) f32, v_in (B, C, N) f32, count (B,) i32, t_out (B, M) f32
    -> (B, C, M) f32. N and M must be multiples of 128 and M of block_m
    (ops.py pads).
    """
    B, N = t_in.shape
    C = v_in.shape[1]
    M = t_out.shape[1]
    if M % block_m:
        raise ValueError(f"M={M} not a multiple of block_m={block_m}")
    # Row axes become a unit sublane axis, so every block's last two
    # dims equal the array's (the TPU tiling rule) for any B.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, M // block_m),
        in_specs=[
            pl.BlockSpec((None, 1, N), lambda b, m, cnt: (b, 0, 0)),
            pl.BlockSpec((None, C, N), lambda b, m, cnt: (b, 0, 0)),
            pl.BlockSpec((None, 1, block_m), lambda b, m, cnt: (b, 0, m)),
        ],
        out_specs=pl.BlockSpec((None, C, block_m),
                               lambda b, m, cnt: (b, 0, m)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, M), jnp.float32),
        interpret=interpret,
    )(count.astype(jnp.int32), t_in.astype(jnp.float32).reshape(B, 1, N),
      v_in.astype(jnp.float32), t_out.astype(jnp.float32).reshape(B, 1, M))
