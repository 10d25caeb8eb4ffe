"""Public wrappers for the delta wire codec (per-block absmax int8/int4).

Array-level API (used by tests/benchmarks):
  encode_array(x)          -> (packed int8, scales f32)   quantize+pack
  decode_array(packed, ..) -> x_hat                       dequantize+unpack
  codec_roundtrip_array(x) -> x_hat                       what the receiver sees

Pytree-level API (used by the engine transitions):
  codec_roundtrip(tree)    — per-leaf round trip, None-leaf aware

Leaves are raveled and zero-padded to a whole number of `block`-element
blocks (one row per block); padding never perturbs a block's absmax, so the
oracle on the unpadded layout and the kernel on the padded one agree bitwise.

Implementation policy (`impl`):
  "ref"    — pure-jnp oracle
  "pallas" — the fused kernel (interpret mode on CPU); requires
             block % 256 == 0 so the int4 halves-packing matches the oracle's
             wire bytes exactly
  "auto"   — oracle on CPU (interpret mode is python-per-tile and the codec
             sits on the engine's per-initiation hot path), kernel elsewhere;
             off the CPU an unaligned block raises, as for "pallas", so the
             device path never silently runs the oracle

`wire_bytes` is the ONE place the compressed payload size is computed —
`ProtocolEngine._wire_bytes` calls it so transfer times, link pricing and the
Eq. 9 cadence all see the real (smaller) payload.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import is_cpu
from repro.kernels.delta_codec.delta_codec import (LANES, dequantize_unpack_2d,
                                                   quantize_pack_2d)
from repro.kernels.delta_codec import ref as ref_lib

CODEC_BITS = {"int8": 8, "int4": 4}
KERNEL_BLOCK_MULTIPLE = 2 * LANES      # pallas path block-alignment requirement


def wire_bytes(n_elems: int, *, codec: str, block: int) -> int:
    """Bytes on the wire for an `n_elems`-element payload: `bits`-bit codes
    plus one f32 scale per `block` elements."""
    bits = CODEC_BITS[codec]
    payload = (n_elems * bits + 7) // 8
    scales = -(-n_elems // block) * 4
    return payload + scales


def _use_ref(impl: str, block: int) -> bool:
    if impl == "ref" or (impl == "auto" and is_cpu()):
        return True
    if block % KERNEL_BLOCK_MULTIPLE:
        raise ValueError(
            f"impl={impl!r} runs the kernel, which requires block % "
            f"{KERNEL_BLOCK_MULTIPLE} == 0 (int4 halves-packing lane "
            f"alignment), got block={block}")
    return False


def _blocked(x, block: int):
    """Flat view padded to (nblocks, block); returns (x2d, n)."""
    n = x.size
    nblocks = -(-n // block)
    flat = x.reshape(-1).astype(jnp.float32)
    pad = nblocks * block - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(nblocks, block), n


def encode_array(x, *, codec: str, block: int, impl: str = "auto"):
    """Fused quantize+pack of one array. Returns (packed int8 (nblocks,
    block*bits//8), scales f32 (nblocks,)) over the zero-padded blocks."""
    bits = CODEC_BITS[codec]
    x2d, _ = _blocked(x, block)
    if _use_ref(impl, block):
        return ref_lib.encode_ref(x2d, bits=bits)
    return quantize_pack_2d(x2d, bits=bits, interpret=is_cpu())


def decode_array(packed, scales, shape, dtype, *, codec: str, block: int,
                 impl: str = "auto"):
    """Fused dequantize+unpack back to `shape`/`dtype` (drops block padding)."""
    bits = CODEC_BITS[codec]
    if _use_ref(impl, block):
        x2d = ref_lib.decode_ref(packed, scales, bits=bits)
    else:
        x2d = dequantize_unpack_2d(packed, scales, bits=bits,
                                   interpret=is_cpu())
    n = 1
    for s in shape:
        n *= s
    return x2d.reshape(-1)[:n].reshape(shape).astype(dtype)


def codec_roundtrip_array(x, *, codec: str, block: int, impl: str = "auto"):
    """decode(encode(x)) — the payload the receiver reconstructs."""
    packed, scales = encode_array(x, codec=codec, block=block, impl=impl)
    return decode_array(packed, scales, x.shape, x.dtype, codec=codec,
                        block=block, impl=impl)


def codec_roundtrip(tree, *, codec: str, block: int, impl: str = "auto"):
    """Pytree-level round trip; None leaves (fragment-extracted trees) pass
    through untouched."""
    return jax.tree.map(
        lambda l: None if l is None else codec_roundtrip_array(
            l, codec=codec, block=block, impl=impl),
        tree, is_leaf=lambda l: l is None)
