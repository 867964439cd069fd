"""Pallas Philox-4x32-10 counter-based PRNG kernel (paper T1, Fig 4.1).

The paper fought Mersenne-Twister pathologies on GPU (624-word per-thread
state, seed hashing, burn-in, striping artefacts — Fig 3.4) and suggests
counter-based generators (PCG) as future work. On TPU the answer is a
counter-based PRNG: stateless, perfectly parallel, no burn-in by
construction. Philox-4x32-10 (Salmon et al., Random123) is implemented with
16-bit-decomposed 32x32->64 multiplies so it lowers on hardware without
64-bit integer support.

Oracle: ``repro.kernels.ref.philox4x32_ref`` (numpy uint64) + published
Random123 known-answer vectors.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PHILOX_M0 = 0xD2511F53
PHILOX_M1 = 0xCD9E8D57
PHILOX_W0 = 0x9E3779B9
PHILOX_W1 = 0xBB67AE85
ROUNDS = 10


def _mulhilo(a: jax.Array, b: int) -> Tuple[jax.Array, jax.Array]:
    """(hi, lo) of the 32x32->64 product, via 16-bit limbs (TPU-safe)."""
    a = a.astype(jnp.uint32)
    bl = jnp.uint32(b & 0xFFFF)
    bh = jnp.uint32((b >> 16) & 0xFFFF)
    al = a & 0xFFFF
    ah = a >> 16
    lo = (a * jnp.uint32(b)).astype(jnp.uint32)          # wraps mod 2^32
    albl = al * bl
    mid1 = ah * bl + (albl >> 16)                        # < 2^32, no wrap
    mid2 = al * bh
    mid = mid1 + mid2                                    # may wrap
    carry = (mid < mid1).astype(jnp.uint32)
    hi = ah * bh + (mid >> 16) + (carry << 16)
    return hi, lo


def philox_rounds(c0, c1, c2, c3, k0, k1):
    """10 Philox rounds on uint32 arrays; returns 4 output words."""
    for r in range(ROUNDS):
        if r > 0:
            k0 = k0 + jnp.uint32(PHILOX_W0)
            k1 = k1 + jnp.uint32(PHILOX_W1)
        hi0, lo0 = _mulhilo(c0, PHILOX_M0)
        hi1, lo1 = _mulhilo(c2, PHILOX_M1)
        c0, c1, c2, c3 = (hi1 ^ c1 ^ k0, lo1, hi0 ^ c3 ^ k1, lo0)
    return c0, c1, c2, c3


def philox_proposal_fields(idx, round_idx, k0, k1, interior: int,
                           nbhd: int):
    """Map Philox counters to one ESCG proposal each (the fused-kernel
    counter layout, DESIGN.md §3): counter = (idx, round_idx, 0, 0) with
    ``idx`` the GLOBAL proposal index (global tile id * K + j), key =
    ``(k0, k1)``. The four output words become (cell, dirn, u_act, u_dom);
    uniform ints via modulus (paper §3.2.1 — bias at most
    max(interior, nbhd) / 2^32 for a 32-bit word reduced mod m), uniform
    floats from the top 24 bits (exact in f32, half-open [0, 1)).

    Keying by global identity only — never by shard layout — is what lets
    every device of the sharded engines regenerate exactly the streams of
    the (tile, proposal) pairs it owns, bit-identical to the single-device
    ``pallas_fused`` engine. Host oracle: ``ref.fused_proposals_ref``.
    """
    idx = idx.astype(jnp.uint32)
    c1 = jnp.full(idx.shape, round_idx, jnp.uint32)
    zeros = jnp.zeros(idx.shape, jnp.uint32)
    x0, x1, x2, x3 = philox_rounds(idx, c1, zeros, zeros, k0, k1)
    cell = (x0 % jnp.uint32(interior)).astype(jnp.int32)
    dirn = (x1 % jnp.uint32(nbhd)).astype(jnp.int32)
    return cell, dirn, _top24_uniform(x2), _top24_uniform(x3)


def _top24_uniform(x):
    """Uniform float32 in [0, 1) from the top 24 bits of a uint32 word.
    They fit in int32, and Mosaic casts int32 (not uint32) to float."""
    top = (x >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(2 ** -24)


def _kernel(seed_ref, out_ref, *, block: int, base_stream: int):
    i = pl.program_id(0)
    k0 = seed_ref[0, 0]
    k1 = seed_ref[0, 1]
    idx = (i * block + jax.lax.iota(jnp.uint32, block))
    c0 = idx
    c1 = jnp.full((block,), base_stream, jnp.uint32)
    c2 = jnp.zeros((block,), jnp.uint32)
    c3 = jnp.zeros((block,), jnp.uint32)
    x0, x1, x2, x3 = philox_rounds(c0, c1, c2, c3, k0, k1)
    out_ref[0, :] = x0
    out_ref[1, :] = x1
    out_ref[2, :] = x2
    out_ref[3, :] = x3


def philox_bits(n: int, seed: Tuple[int, int], stream: int = 0,
                block: int = 1024, interpret: bool = False) -> jax.Array:
    """Generate ``n`` uint32 words (4 words per counter, n rounded up to
    4*block internally, truncated on return)."""
    n_ctr = -(-n // 4)
    n_blocks = -(-n_ctr // block)
    seed_arr = jnp.array([[seed[0], seed[1]]], dtype=jnp.uint32)
    kern = functools.partial(_kernel, block=block, base_stream=stream)
    out = pl.pallas_call(
        kern,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((4, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((4, n_blocks * block), jnp.uint32),
        interpret=interpret,
    )(seed_arr)
    return out.T.reshape(-1)[:n]


def philox_uniform(n: int, seed: Tuple[int, int], stream: int = 0,
                   block: int = 1024, interpret: bool = False) -> jax.Array:
    """n float32 uniforms in [0, 1): top 24 bits * 2^-24 (exact in f32,
    guarantees the half-open interval — bits * 2^-32 can round to 1.0)."""
    bits = philox_bits(n, seed, stream, block, interpret)
    return (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)
