"""Jit'd public wrappers over the Pallas kernels.

Each call picks the kernel's execution mode from the backend it is traced
for: on a TPU the kernels compile natively; on the CPU (unit tests) they
run through ``interpret=True``, which executes the kernel body with
identical semantics. Any other backend has no lowering here and raises —
nothing falls back to the interpreter in silence.
"""
from __future__ import annotations

import jax

from repro.kernels import adc as _adc
from repro.kernels import hamming as _hamming
from repro.kernels import l2dist as _l2dist
from repro.kernels import lsh_hash as _lsh_hash


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel lowering for backend {backend!r}")


def lsh_hash(x, a, b, w, **kw):
    return _lsh_hash.lsh_hash(x, a, b, w, interpret=_interpret(), **kw)


def l2dist(x, q, **kw):
    return _l2dist.l2dist(x, q, interpret=_interpret(), **kw)


def adc(codes, lut, **kw):
    return _adc.adc(codes, lut, interpret=_interpret(), **kw)


def adc_batch(codes, luts, **kw):
    return _adc.adc_batch(codes, luts, interpret=_interpret(), **kw)


def adc_q8(codes, qlut, **kw):
    return _adc.adc_q8(codes, qlut, interpret=_interpret(), **kw)


def adc_batch_q8(codes, qluts, **kw):
    return _adc.adc_batch_q8(codes, qluts, interpret=_interpret(), **kw)


def hamming(bucket_codes, qcode, **kw):
    return _hamming.hamming(bucket_codes, qcode, interpret=_interpret(), **kw)
