"""Wire quantization codecs for compressed collectives (DESIGN.md §17).

The cross-island legs of a heterogeneous fleet are the bandwidth floor of
every plan (paper §5.2; H2 and HETHUB in PAPERS.md reach the same
conclusion for 1,000+-chip mixed fleets), and ``cross_dtype`` already
narrows the wire to bf16.  This module goes further: per-chunk absmax
scaling to **int8** (4x fewer wire bytes than f32) or an e4m3-style **fp8**
software codec, with an f32 accumulator on the receive side and the scale
carried alongside the payload as a sidecar.

Wire format (DESIGN.md §17): a payload of N elements is flattened,
zero-padded to a multiple of ``DEFAULT_CHUNK``, and encoded as

  * ``codes``  — one byte per element (int8 two's-complement in [-127, 127]
    for the ``"int8"`` codec; e4m3 sign/exp/mantissa bits for ``"fp8"``),
    kept in the *original payload shape* so the transport stripe schedule
    slices it exactly like an uncompressed hop;
  * ``scales`` — one f32 per chunk, shape (nchunks, 1): the chunk's absmax
    mapped to the codec's top code (127 for int8, 448 for e4m3).  An
    all-zero chunk stores scale 1 so decode is division-free.

Sidecar overhead: 4 / DEFAULT_CHUNK bytes per element (< 1%).

Three execution paths per TACC platform, bit-equivalent **under jit** —
the only context the ring dispatches them in (asserted by
tests/test_kernels.py; eager-vs-jit comparisons can drift one ulp from
XLA's FMA fusion of the decode multiply-add): ``cpu`` pure-jnp reference,
``tpu`` the Pallas kernels, ``interpret`` the same kernel bodies in
interpreter mode — the same contract as ``collective_reduce``.  The fp8 codec is a
*software* codec (jnp bit math) on every platform: its consumer is the
CPU/interpret equivalence lane, while the TPU fast path quantizes int8.

Error feedback (§17): :func:`ef_compress` implements the standard EF
transform — compress ``x + residual``, return the on-grid value and the new
residual ``(x + residual) - compressed`` — whose telescoping property
(sum of compressed updates + final residual == sum of true updates, exact
in f32 when the grid values are exactly representable) is what preserves
convergence under aggressive wire compression (tests/test_properties.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import compat, tacc
from repro.kernels.collective_reduce import ragged_block_call

CODECS = ("int8", "fp8")
DEFAULT_CHUNK = 512          # elements per scale (f32 sidecar: 4B / chunk)
SCALE_BYTES = 4              # sidecar bytes per chunk
INT8_TOP = 127.0             # symmetric int8 top code
E4M3_MAX = 448.0             # e4m3fn max finite (exp 15, mantissa 6)


def wire_bytes_per_elem(codec: str | None, itemsize: int = 4,
                        chunk: int = DEFAULT_CHUNK) -> float:
    """Bytes on the wire per payload element under ``codec`` (None -> the
    uncompressed itemsize).  Includes the scale sidecar — the simulator's
    pricing term (DESIGN.md §17)."""
    if codec is None:
        return float(itemsize)
    if codec not in CODECS:
        raise ValueError(f"unknown wire_quant codec {codec!r}; "
                         f"expected one of {CODECS}")
    return 1.0 + SCALE_BYTES / float(chunk)


# ---------------------------------------------------------------------------
# e4m3-style fp8 software codec: value grid sign * q * 2^(e-3) with
# q in [8, 15] for normals (exp field e+7 in [1, 15]), q in [0, 7] denormals
# (exp field 0, e = -6).  Mantissa 7 at exp 15 is NaN in e4m3fn, so the top
# finite code is 448 = 14 * 2^5; encode saturates there.
# ---------------------------------------------------------------------------

def encode_e4m3(y: jax.Array) -> jax.Array:
    """f32 -> uint8 e4m3 bit codes (round-to-nearest, saturating at 448)."""
    y = y.astype(jnp.float32)
    sign = (y < 0).astype(jnp.uint8)
    a = jnp.minimum(jnp.abs(y), E4M3_MAX)
    e = jnp.clip(jnp.floor(jnp.log2(jnp.where(a > 0, a, 1.0))), -6.0, 8.0)
    step = jnp.exp2(e - 3.0)
    q = jnp.round(a / step)
    roll = q >= 16.0                      # mantissa overflow -> next exponent
    e = jnp.where(roll, jnp.minimum(e + 1.0, 8.0), e)
    q = jnp.where(roll, 8.0, q)
    q = jnp.where(e >= 8.0, jnp.minimum(q, 14.0), q)   # 0x7f is NaN: cap 448
    q = jnp.where(a > 0, q, 0.0)
    norm = q >= 8.0
    exp_field = jnp.where(norm, e + 7.0, 0.0).astype(jnp.uint8)
    mant = jnp.where(norm, q - 8.0, q).astype(jnp.uint8)
    return (sign << 7) | (exp_field << 3) | mant


def decode_e4m3(bits: jax.Array) -> jax.Array:
    """uint8 e4m3 bit codes -> f32 values."""
    bits = bits.astype(jnp.uint8)
    sign = jnp.where((bits >> 7) > 0, -1.0, 1.0)
    exp_field = ((bits >> 3) & 0xF).astype(jnp.float32)
    mant = (bits & 0x7).astype(jnp.float32)
    norm = exp_field > 0
    q = jnp.where(norm, mant + 8.0, mant)
    e = jnp.where(norm, exp_field - 7.0, -6.0)
    return sign * q * jnp.exp2(e - 3.0)


# ---------------------------------------------------------------------------
# Reference codecs (pure jnp): (nchunks, chunk) f32 <-> codes + scales.
# ---------------------------------------------------------------------------

def _chunk_scale(x2: jax.Array, top: float) -> jax.Array:
    absmax = jnp.max(jnp.abs(x2), axis=1, keepdims=True)
    return jnp.where(absmax > 0, absmax / top, 1.0)


def wire_quantize_ref(x2: jax.Array, *, codec: str = "int8"):
    """x2 (nchunks, chunk) f32 -> (codes (nchunks, chunk), scales
    (nchunks, 1) f32).  Pure-jnp oracle for both codecs."""
    x2 = x2.astype(jnp.float32)
    if codec == "int8":
        scale = _chunk_scale(x2, INT8_TOP)
        codes = jnp.clip(jnp.round(x2 / scale),
                         -INT8_TOP, INT8_TOP).astype(jnp.int8)
        return codes, scale
    if codec == "fp8":
        scale = _chunk_scale(x2, E4M3_MAX)
        return encode_e4m3(x2 / scale), scale
    raise ValueError(f"unknown wire_quant codec {codec!r}")


def wire_dequant_accum_ref(acc2: jax.Array, codes2: jax.Array,
                           scales: jax.Array, *, codec: str = "int8"):
    """acc2 (nchunks, chunk) f32 + decode(codes2, scales) -> f32."""
    if codec == "int8":
        vals = codes2.astype(jnp.float32)
    elif codec == "fp8":
        vals = decode_e4m3(codes2)
    else:
        raise ValueError(f"unknown wire_quant codec {codec!r}")
    return acc2.astype(jnp.float32) + vals * scales.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Pallas kernels (int8 path; fp8 stays on the software codec — see module
# docstring).  Blockwise over chunk rows via the shared ragged plumbing.
# ---------------------------------------------------------------------------

_BLOCK_ROWS = 8


def _quant_int8_kernel(x_ref, codes_ref, scales_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / INT8_TOP, 1.0)
    scales_ref[...] = scale
    codes_ref[...] = jnp.clip(jnp.round(x / scale),
                              -INT8_TOP, INT8_TOP).astype(jnp.int8)


def _dq_accum_kernel(acc_ref, codes_ref, scales_ref, o_ref):
    o_ref[...] = (acc_ref[...].astype(jnp.float32) +
                  codes_ref[...].astype(jnp.float32) * scales_ref[...])


def wire_quantize_pallas(x2: jax.Array, *, codec: str = "int8",
                         interpret: bool = False):
    """Pallas quantize: the chunk dimension must live in one block (absmax
    is a whole-chunk reduction), so the block is (rows, chunk)."""
    if codec != "int8":                    # fp8: software codec everywhere
        return wire_quantize_ref(x2, codec=codec)
    n, chunk = x2.shape
    return ragged_block_call(
        _quant_int8_kernel, [x2.astype(jnp.float32)],
        [jax.ShapeDtypeStruct((n, chunk), jnp.int8),
         jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        block=(_BLOCK_ROWS, chunk), interpret=interpret)


def wire_dequant_accum_pallas(acc2: jax.Array, codes2: jax.Array,
                              scales: jax.Array, *, codec: str = "int8",
                              interpret: bool = False):
    """Pallas dequantize-accumulate: per-row scale sidecar rides the shared
    ragged pad-and-slice (``collective_reduce.ragged_block_call``)."""
    if codec != "int8":
        return wire_dequant_accum_ref(acc2, codes2, scales, codec=codec)
    n, chunk = acc2.shape
    return ragged_block_call(
        _dq_accum_kernel,
        [acc2.astype(jnp.float32), codes2, scales.astype(jnp.float32)],
        [jax.ShapeDtypeStruct((n, chunk), jnp.float32)],
        block=(_BLOCK_ROWS, min(chunk, 256)), interpret=interpret)


tacc.register("wire_quantize", "cpu", default=True)(wire_quantize_ref)
tacc.register("wire_quantize", "tpu")(
    compat.manual_region(wire_quantize_pallas))
tacc.register("wire_quantize", "interpret")(
    functools.partial(wire_quantize_pallas, interpret=True))
tacc.register("wire_dequant_accum", "cpu", default=True)(
    wire_dequant_accum_ref)
tacc.register("wire_dequant_accum", "tpu")(
    compat.manual_region(wire_dequant_accum_pallas))
tacc.register("wire_dequant_accum", "interpret")(
    functools.partial(wire_dequant_accum_pallas, interpret=True))


# ---------------------------------------------------------------------------
# Shape-polymorphic front doors (the ring / trainer entry points).
# ---------------------------------------------------------------------------

def _to_chunks(flat: jax.Array, chunk: int) -> jax.Array:
    pad = (-flat.shape[0]) % chunk
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, chunk)


def quantize(x: jax.Array, *, codec: str = "int8",
             chunk: int = DEFAULT_CHUNK):
    """x (any shape) -> (codes, scales): codes byte-per-element in x's
    shape, scales (nchunks, 1) f32 over the flattened, chunk-padded view.
    Platform-resolved via TACC (Pallas kernel on tpu/interpret)."""
    x2 = _to_chunks(x.astype(jnp.float32).reshape(-1), chunk)
    codes2, scales = tacc.dispatch("wire_quantize", x2, codec=codec)
    return codes2.reshape(-1)[:x.size].reshape(x.shape), scales


def dequantize_accumulate(acc: jax.Array, codes: jax.Array,
                          scales: jax.Array, *, codec: str = "int8",
                          chunk: int = DEFAULT_CHUNK) -> jax.Array:
    """acc (f32, codes.shape) + decode(codes, scales) -> f32.  The receive
    side of a quantized ring hop: the accumulator never narrows."""
    acc2 = _to_chunks(acc.astype(jnp.float32).reshape(-1), chunk)
    codes2 = _to_chunks(codes.reshape(-1), chunk)
    out2 = tacc.dispatch("wire_dequant_accum", acc2, codes2, scales,
                         codec=codec)
    return out2.reshape(-1)[:acc.size].reshape(acc.shape)


def dequantize(codes: jax.Array, scales: jax.Array, *, codec: str = "int8",
               chunk: int = DEFAULT_CHUNK) -> jax.Array:
    """decode(codes, scales) -> f32 in codes' shape."""
    return dequantize_accumulate(jnp.zeros(codes.shape, jnp.float32), codes,
                                 scales, codec=codec, chunk=chunk)


def compress(x: jax.Array, *, codec: str = "int8",
             chunk: int = DEFAULT_CHUNK) -> jax.Array:
    """Quantize-dequantize round trip: x projected onto the codec grid
    (f32).  Idempotent on already-on-grid inputs whose chunks carry a
    top-code element (the hypothesis property, tests/test_properties.py)."""
    codes, scales = quantize(x, codec=codec, chunk=chunk)
    return dequantize(codes, scales, codec=codec, chunk=chunk)


def ef_compress(x: jax.Array, residual: jax.Array, *, codec: str = "int8",
                chunk: int = DEFAULT_CHUNK):
    """Error-feedback compression (DESIGN.md §17): compress
    ``x + residual``, carry the quantization error into the new residual.

    Returns ``(compressed, new_residual)`` with the telescoping invariant
    ``sum(compressed_t) + residual_T == sum(x_t) + residual_0`` exact in
    f32 whenever the subtraction is (Sterbenz: compressed is within 2x of
    the input for on-scale values).
    """
    y = x.astype(jnp.float32) + residual.astype(jnp.float32)
    c = compress(y, codec=codec, chunk=chunk)
    return c, y - c
