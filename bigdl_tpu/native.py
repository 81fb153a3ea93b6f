"""ctypes bindings for the native host quantization library (csrc/).

Role-equivalent of the reference's ctypes layer over its prebuilt C++
quant kernels (`ggml/model/llama/llama_cpp.py` bindings consumed by
`low_bit_linear.py:104-258` in /root/reference), except the library is
built from source on first use (g++ is part of the toolchain; there is
no prebuilt-wheel channel). Falls back to the pure-jnp numerics when the
toolchain is unavailable — behavior is bit-identical either way.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "csrc", "quant_kernels.cpp")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _build_dir() -> str:
    d = os.environ.get("BIGDL_TPU_NATIVE_CACHE") or os.path.join(
        os.path.expanduser("~"), ".cache", "bigdl_tpu"
    )
    os.makedirs(d, exist_ok=True)
    return d


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("BIGDL_TPU_DISABLE_NATIVE"):
            return None
        if not os.path.exists(_SRC):
            return None
        try:
            with open(_SRC, "rb") as f:
                tag = hashlib.sha256(f.read()).hexdigest()[:16]
            so = os.path.join(_build_dir(), f"quant_kernels_{tag}.so")
            if not os.path.exists(so):
                # a name of this process's own: several processes that
                # start with no build yet (test workers in a fresh HOME)
                # each compile and each rename a whole file; with one
                # shared name all but the first lost theirs half-written
                tmp = f"{so}.{os.getpid()}.tmp"
                subprocess.run(
                    [
                        "g++", "-O3", "-march=native", "-fopenmp", "-shared",
                        "-fPIC", "-o", tmp, _SRC,
                    ],
                    check=True, capture_output=True, timeout=120,
                )
                os.replace(tmp, so)
            lib = ctypes.CDLL(so)
            I64, F32P = ctypes.c_int64, np.ctypeslib.ndpointer(np.float32, flags="C")
            U8P = np.ctypeslib.ndpointer(np.uint8, flags="C")
            U16P = np.ctypeslib.ndpointer(np.uint16, flags="C")
            I8P = np.ctypeslib.ndpointer(np.int8, flags="C")
            I32P = np.ctypeslib.ndpointer(np.int32, flags="C")
            lib.quantize_sym_int4.argtypes = [F32P, I64, I64, U8P, U16P]
            lib.quantize_asym_int4.argtypes = [F32P, I64, I64, U8P, U16P, U16P]
            lib.quantize_sym_int8.argtypes = [F32P, I64, I64, I8P, U16P]
            lib.quantize_codebook4.argtypes = [
                F32P, I64, I64, I64, F32P, I32P, ctypes.c_float, U8P, U16P,
            ]
            lib.dequantize_sym_int4.argtypes = [U8P, U16P, I64, I64, F32P]
            _LIB = lib
        except Exception:
            _LIB = None
        return _LIB


def available() -> bool:
    return _load() is not None


_CODEBOOK4 = ("nf4", "fp4")
SUPPORTED = ("sym_int4", "asym_int4", "sym_int8") + _CODEBOOK4


def quantize_np(x: np.ndarray, qtype: str):
    """Quantize [.., rows, k] fp32 → (data, scales f16, mins|None) numpy,
    layouts identical to quant.numerics.quantize_blockwise. Returns None
    when the native library is unavailable or the qtype unsupported."""
    lib = _load()
    if lib is None or qtype not in SUPPORTED:
        return None
    from bigdl_tpu.quant.numerics import _codebook_tables
    from bigdl_tpu.quant.qtypes import resolve_qtype

    spec = resolve_qtype(qtype)
    x = np.ascontiguousarray(x, np.float32)
    k = x.shape[-1]
    if k % spec.block_size != 0:
        return None
    lead = x.shape[:-1]
    rows = int(np.prod(lead)) if lead else 1
    nb = k // spec.block_size
    scales = np.empty((rows, nb), np.uint16)
    x2 = x.reshape(rows, k)

    if qtype == "sym_int4":
        data = np.empty((rows, k // 2), np.uint8)
        lib.quantize_sym_int4(x2, rows, k, data, scales)
        mins = None
    elif qtype == "asym_int4":
        data = np.empty((rows, k // 2), np.uint8)
        mins = np.empty((rows, nb), np.uint16)
        lib.quantize_asym_int4(x2, rows, k, data, scales, mins)
    elif qtype == "sym_int8":
        data = np.empty((rows, k), np.int8)
        lib.quantize_sym_int8(x2, rows, k, data, scales)
        mins = None
    else:  # nf4 / fp4
        cb, order, boundaries = _codebook_tables(qtype)
        data = np.empty((rows, k // 2), np.uint8)
        lib.quantize_codebook4(
            x2, rows, k, spec.block_size,
            np.ascontiguousarray(boundaries, np.float32),
            np.ascontiguousarray(order, np.int32),
            float(np.max(np.abs(cb))), data, scales,
        )
        mins = None

    data = data.reshape(*lead, data.shape[-1])
    scales = scales.reshape(*lead, nb).view(np.float16)
    if mins is not None:
        mins = mins.reshape(*lead, nb).view(np.float16)
    return data, scales, mins


def quantize_to_qtensor(x: np.ndarray, qtype: str):
    """NumPy → QTensor via the native packer; None if unavailable."""
    out = quantize_np(x, qtype)
    if out is None:
        return None
    import jax.numpy as jnp

    from bigdl_tpu.quant import QTensor

    data, scales, mins = out
    return QTensor(
        data=jnp.asarray(data),
        scales=jnp.asarray(scales),
        mins=None if mins is None else jnp.asarray(mins),
        qtype=qtype,
    )
