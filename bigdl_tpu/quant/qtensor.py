"""QTensor — a quantized tensor as a JAX pytree node.

The TPU-native counterpart of the reference's `FP4Params`
(/root/reference python/llm/src/ipex_llm/transformers/low_bit_linear.py:312):
instead of a torch.nn.Parameter subclass holding a ggml byte blob, a QTensor
is a registered dataclass whose array fields (packed codes, scales, mins)
are ordinary JAX arrays. That makes quantized weights first-class citizens
of every JAX transform: they can be donated, sharded with
`jax.sharding.NamedSharding`, carried through `lax.scan` over stacked
layers, and saved/restored as pytree leaves.

The logical shape is derived from the storage shape, so a QTensor sliced
along a leading (layer-stacking) axis by `lax.scan` remains self-consistent
without any static-metadata surgery.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.quant.numerics import dequantize_blockwise, quantize_blockwise
from bigdl_tpu.quant.qtypes import QTypeSpec, resolve_qtype


# array fields of a QTensor, in declaration order; sub_scales/sub_mins
# carry the integer sub-block scales of two-level (k-quant) formats
ARRAY_FIELDS = ("data", "scales", "mins", "sub_scales", "sub_mins")

# what a serving program derives ONCE from `scales` / `mins` for the fused
# kernels (`ops/linear.prepare_scale_bits`): the same float16 values as
# uint16 bits in the order the kernel's tile plan reads them, `bits_layout`
# naming that order. Not ARRAY_FIELDS: a QTensor rebuilt field-wise (sliced,
# stacked, sharded, saved) drops them and its calls read the float16 fields.
KERNEL_FIELDS = ("scale_bits", "min_bits")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QTensor:
    data: jax.Array
    scales: jax.Array
    mins: Optional[jax.Array] = None
    qtype: str = dataclasses.field(metadata=dict(static=True), kw_only=True)
    sub_scales: Optional[jax.Array] = None
    sub_mins: Optional[jax.Array] = None
    scale_bits: Optional[jax.Array] = None
    min_bits: Optional[jax.Array] = None
    bits_layout: Optional[str] = dataclasses.field(
        default=None, metadata=dict(static=True), kw_only=True)

    @property
    def spec(self) -> QTypeSpec:
        return resolve_qtype(self.qtype)

    @property
    def shape(self) -> tuple[int, ...]:
        spec = self.spec
        if spec.storage == "packed_u8":
            return (*self.data.shape[:-1], self.data.shape[-1] * 2)
        if spec.storage == "packed_planes":
            # planes store sum(planes) == spec.bits bits per element
            return (*self.data.shape[:-1], self.data.shape[-1] * 8 // spec.bits)
        return tuple(self.data.shape)

    @property
    def ndim(self) -> int:
        return self.data.ndim

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        return dequantize_blockwise(
            self.data, self.scales, self.mins, self.spec, dtype,
            sub_scales=self.sub_scales, sub_mins=self.sub_mins,
        )

    def map_arrays(self, fn) -> "QTensor":
        """New QTensor with `fn` applied to every non-None array field —
        the one place slice/stack/concat/shard rebuilds go through, so
        field additions don't scatter across call sites."""
        kw = {
            f: (None if getattr(self, f) is None else fn(getattr(self, f)))
            for f in ARRAY_FIELDS
        }
        return QTensor(qtype=self.qtype, **kw)

    def nbytes(self) -> int:
        n = 0
        for f in ARRAY_FIELDS:
            v = getattr(self, f)
            if v is not None:
                n += v.size * v.dtype.itemsize
        return n


def without_scale_bits(tree):
    """`tree` with every QTensor's KERNEL_FIELDS dropped: the tree as
    `optimize_model` returns it, for whoever shards, saves or compares
    it."""
    is_q = lambda x: isinstance(x, QTensor)  # noqa: E731
    return jax.tree.map(
        lambda w: dataclasses.replace(
            w, bits_layout=None, **dict.fromkeys(KERNEL_FIELDS))
        if is_q(w) else w, tree, is_leaf=is_q)


def map_arrays_multi(ws: list["QTensor"], fn) -> "QTensor":
    """Combine several same-qtype QTensors field-wise (stack/concat):
    `fn` receives the list of arrays for each non-None field."""
    kw = {
        f: (None if getattr(ws[0], f) is None
            else fn([getattr(w, f) for w in ws]))
        for f in ARRAY_FIELDS
    }
    return QTensor(qtype=ws[0].qtype, **kw)


# k-quant fallbacks for tensors whose contraction dim is not a multiple
# of the 256-element super-block — same policy as llama.cpp, which drops
# incompatible tensors to a 32-block format of comparable width.
_KQUANT_FALLBACK = {
    "q2_k": "sym_int4", "q3_k": "sym_int4", "q4_k": "sym_int4",
    "q5_k": "sym_int5", "q6_k": "sym_int8",
}


def _effective_spec(last_dim: int, qtype: str):
    """The spec quantize() will actually use for a given last dim —
    including the k-quant superblock fallback."""
    spec = resolve_qtype(qtype)
    if (spec.superblock and last_dim % spec.superblock
            and spec.name in _KQUANT_FALLBACK):
        spec = resolve_qtype(_KQUANT_FALLBACK[spec.name])
    return spec


def quantize(x: jax.Array, qtype: str) -> QTensor:
    """Quantize `x` along its last axis into a QTensor.

    Equivalent of the reference's `FP4Params.quantize`
    (low_bit_linear.py:348): blockwise along the contraction axis.
    """
    spec = resolve_qtype(qtype)
    if spec.is_dense:
        raise ValueError(f"qtype {qtype} is dense; keep the array as-is")
    spec = _effective_spec(x.shape[-1], qtype)
    fields = quantize_blockwise(x, spec)
    return QTensor(qtype=spec.name, **fields)


def quantize_or_dense(x: jax.Array, qtype: str, what: str = "weight"):
    """quantize(), but weights whose last dim cannot take the format
    (not divisible by the effective block size, after the k-quant
    fallback) stay dense with a warning instead of failing the whole
    model — the reference's per-module gating behavior (convert.py's
    is_linear_module checks). Shared by every family's quantize_params."""
    spec = _effective_spec(x.shape[-1], qtype)
    if x.shape[-1] % spec.block_size:
        import warnings

        warnings.warn(
            f"{what}: last dim {x.shape[-1]} not divisible by "
            f"{spec.name}'s block size {spec.block_size}; keeping this "
            "weight dense"
        )
        return x
    return quantize(x, qtype)


def dequantize(qt: QTensor, dtype=jnp.bfloat16) -> jax.Array:
    return qt.dequantize(dtype)
