"""Newton-Schulz orthogonalization as one Pallas TPU kernel per matrix.

Quintic Newton-Schulz (five iterations of X <- aX + (bA + cA·A)·X with
A = X·Xᵀ) reads and writes three arrays per iteration when XLA runs it:
the iterate X, the Gram matrix A and the polynomial B.  A and B are small
(the Gram side squared) but round-trip HBM five times per matrix.  Here
the grid walks the matrices of a stack, one per step, and the whole
iteration runs inside that step:

* A (float32) and B (bfloat16, only ever a matmul operand) live in VMEM
  scratch and never reach HBM;
* the iterate is walked in tiles along its long side.  Each tile of one
  iteration is updated (``a·x + B·x``) and at once folded into the next
  iteration's Gram matrix (``A += x·xᵀ``), so an iteration is one sweep;
* **resident**: where the matrix fits, the whole iterate sits in the
  output block (VMEM) and HBM sees G once in and X once out, both
  double-buffered across the stack by the grid pipeline;
* **streamed**: otherwise G and X stay in HBM and each sweep streams the
  tiles through two VMEM buffers each way, the next tile's read and the
  last tile's write in flight while the MXU works on the current one.
  A sweep reads X once and writes it once; G is read three times (the
  norm, the first Gram matrix, the first update).

Orientation: wide (m <= n) tiles are column blocks (m, t) with
A += x·xᵀ and x <- a·x + B·x; tall tiles are row blocks (t, n) with
A += xᵀ·x and x <- a·x + x·B.  No transpose of X is written anywhere.

Rounding, as a float32 matmul at JAX's default precision on a TPU: every
matmul takes bfloat16 operands (round-to-nearest-even of the float32
values) and accumulates in float32; ``a·x`` and ``b·A`` use the float32
values; X stays float32 between iterations.  ``ref.py`` writes the same
rounding points out in jnp.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

COEFFS = (3.4445, -4.7750, 2.0315)
LANE = 128
TILE = 512                 # widest tile along the long side
ROW_BLOCK = 128            # rows of B computed per matmul
# Bytes of VMEM the kernel may ask for: v5e's TensorCore has 128 MiB.
VMEM_BUDGET = 100 * 2 ** 20
_SLACK = 8 * 2 ** 20       # Mosaic's internal scratch and spill room

_BF = jnp.bfloat16
_F32 = jnp.float32


class Plan(NamedTuple):
    """How one (m, n) matrix runs: tile width along the long side, whether
    the iterate stays in VMEM, and the VMEM the kernel asks for."""
    tile: int
    resident: bool
    vmem_bytes: int


def _gram_bytes(s: int) -> int:
    # A (f32), A and B (bf16) scratch, and a tile's (s, s) Gram product
    return (4 + 2 + 2) * s * s + 4 * s * s


def _tile_bytes(s: int, t: int) -> int:
    # one tile's f32 value, its bf16 copy and a matmul product
    return (4 + 2 + 4) * s * t


def plan(m: int, n: int) -> "Plan | None":
    """The kernel's plan for an (m, n) matrix, or None where it does not
    apply: a side not a multiple of 128, or a Gram side whose scratch does
    not fit the VMEM budget.  A resident iterate before a streamed one, a
    wider tile before a narrower one.  A pure function of the shape."""
    if m % LANE or n % LANE or m == 0 or n == 0:
        return None
    s, l = min(m, n), max(m, n)
    widths = [w for w in range(min(TILE, l), 0, -LANE) if l % w == 0]
    for resident in (True, False):
        for t in widths:
            # resident: G and X blocks, double-buffered over the stack;
            # streamed: two in and two out tile buffers
            need = (_gram_bytes(s) + _tile_bytes(s, t) + _SLACK
                    + 16 * (m * n if resident else s * t))
            if need <= VMEM_BUDGET:
                return Plan(t, resident, need)
    return None


def ns_flops(shape, steps: int) -> int:
    """Matmul FLOPs of Newton-Schulz on a stack of (m, n) matrices: per
    iteration a Gram matrix and a polynomial on the small side s and its
    application, 4·s²·l + 2·s³."""
    *lead, m, n = shape
    s, l = min(m, n), max(m, n)
    return math.prod(lead) * steps * (4 * s * s * l + 2 * s ** 3)


def _sumsq(x):
    return jnp.sum(jnp.sum(x * x, axis=0, keepdims=True), axis=1,
                   keepdims=True)


_NN = (((1,), (0,)), ((), ()))       # x·y
_NT = (((1,), (1,)), ((), ()))       # x·yᵀ
_TN = (((0,), (0,)), ((), ()))       # xᵀ·y


def _dot(x, y, dims):
    """bfloat16 operands, float32 accumulation: one MXU pass.  The product
    of two bfloat16 values is exact, so the precision asked of the caller's
    float32 matmuls does not apply (Mosaic refuses HIGHEST on bfloat16)."""
    return jax.lax.dot_general(x.astype(_BF), y.astype(_BF), dims,
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=_F32)


def _view(ref, j, t: int, tall: bool):
    sl = pl.ds(pl.multiple_of(j * t, t), t)
    return ref.at[sl, :] if tall else ref.at[:, sl]


def _sweep_vmem(n_tiles, src, dst, fn, carry):
    """fn over the tiles of a VMEM-resident matrix, results stored in dst."""
    def body(j, carry):
        y, carry = fn(src(j)[...], carry)
        if dst is not None:
            dst(j)[...] = y
        return carry
    return jax.lax.fori_loop(0, n_tiles, body, carry)


def _sweep_hbm(n_tiles, ibuf, obuf, isem, osem, src, dst, fn, carry):
    """fn over the tiles of a matrix in HBM: tile j+1 is read and tile j-1
    written while fn works on tile j."""
    def fetch(j, slot):
        return pltpu.make_async_copy(src(j), ibuf.at[slot], isem.at[slot])

    def flush(j, slot):
        return pltpu.make_async_copy(obuf.at[slot], dst(j), osem.at[slot])

    fetch(0, 0).start()

    def body(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_tiles)
        def _():
            fetch(j + 1, 1 - slot).start()

        fetch(j, slot).wait()
        y, carry = fn(ibuf[slot], carry)
        if dst is not None:
            @pl.when(j >= 2)
            def _():
                flush(j - 2, slot).wait()

            obuf[slot] = y
            flush(j, slot).start()
        return carry

    carry = jax.lax.fori_loop(0, n_tiles, body, carry)
    if dst is not None:
        for j in range(max(n_tiles - 2, 0), n_tiles):
            flush(j, j % 2).wait()
    return carry


def _ns_kernel(g_ref, o_ref, a_ref, ab_ref, b_ref, *bufs, steps: int,
               eps: float, tall: bool, t: int, n_tiles: int, resident: bool):
    a, b, c = COEFFS
    s = a_ref.shape[0]
    if resident:
        G = lambda j: _view(g_ref, j, t, tall)
        X = lambda j: _view(o_ref, j, t, tall)
        sweep = functools.partial(_sweep_vmem, n_tiles)
    else:
        i = pl.program_id(0)
        G = lambda j: _view(g_ref.at[i], j, t, tall)
        X = lambda j: _view(o_ref.at[i], j, t, tall)
        sweep = functools.partial(_sweep_hbm, n_tiles, *bufs)

    ss = sweep(G, None, lambda x, acc: (None, acc + _sumsq(x)),
               jnp.zeros((1, 1), _F32))
    d = jnp.sqrt(ss) + eps

    def fold(x):                           # A += x·xᵀ (tall: xᵀ·x)
        a_ref[...] += _dot(x, x, _TN if tall else _NT)

    def first(x, carry):
        x = x / d
        fold(x)
        return x, carry

    a_ref[...] = jnp.zeros_like(a_ref)
    # streamed, X0 is written only where no iteration follows to write X
    sweep(G, X if resident or steps == 0 else None, first, ())

    def iteration(src, scale: bool, last: bool):
        ab_ref[...] = a_ref[...].astype(_BF)

        @pl.loop(0, s // ROW_BLOCK)
        def _(r):
            r = pl.ds(pl.multiple_of(r * ROW_BLOCK, ROW_BLOCK), ROW_BLOCK)
            b_ref[r, :] = (b * a_ref[r, :] + c * _dot(
                ab_ref[r, :], ab_ref[...], _NN)).astype(_BF)
        if not last:
            a_ref[...] = jnp.zeros_like(a_ref)

        def update(x, carry):
            if scale:
                x = x / d
            x = a * x + (_dot(x, b_ref[...], _NN) if tall
                         else _dot(b_ref[...], x, _NN))
            if not last:
                fold(x)
            return x, carry

        sweep(src, X, update, ())

    # the streamed first update re-reads G; the resident one holds X0
    k0 = 0 if resident else 1
    if not resident and steps:
        iteration(G, True, steps == 1)
    if steps - 1 > k0:
        def middle(_, carry):
            iteration(X, False, False)
            return carry
        jax.lax.fori_loop(k0, steps - 1, middle, 0)
    if steps > k0:
        iteration(X, False, True)


def newton_schulz_fwd(G, *, steps: int, eps: float, plan: Plan,
                      interpret):
    """G: (N, m, n) float32 with (m, n) as ``plan`` allows.  Returns the
    orthogonalized stack, float32."""
    N, m, n = G.shape
    tall = m > n
    s = min(m, n)
    t = plan.tile
    tile_shape = (t, s) if tall else (s, t)
    kernel = functools.partial(
        _ns_kernel, steps=steps, eps=eps, tall=tall, t=t,
        n_tiles=max(m, n) // t, resident=plan.resident)
    scratch = [pltpu.VMEM((s, s), _F32), pltpu.VMEM((s, s), _BF),
               pltpu.VMEM((s, s), _BF)]
    if plan.resident:
        spec = pl.BlockSpec((None, m, n), lambda i: (i, 0, 0))
    else:
        spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch += [pltpu.VMEM((2,) + tile_shape, _F32),
                    pltpu.VMEM((2,) + tile_shape, _F32),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2,))]
    # G in, X out; streamed: G read thrice, X read and written each sweep
    passes = 2 if plan.resident else 3 + 2 * steps - 1
    return pl.pallas_call(
        kernel,
        grid=(N,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((N, m, n), _F32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=plan.vmem_bytes),
        cost_estimate=pl.CostEstimate(flops=ns_flops(G.shape, steps),
                                      transcendentals=0,
                                      bytes_accessed=4 * passes * N * m * n),
        interpret=interpret,
        name="newton_schulz",
    )(G)
