"""Compile checks for one described TPU v5e chip — no chip attached.

The chip's own compiler lowers each Pallas kernel and the served estimate
step at serving widths, which interpret-mode runs on the CPU cannot
check: a kernel that the TPU lowering refuses (an unsupported primitive, a
misaligned block, too much VMEM) or a step that does not fit the chip's
HBM fails here. The topology is described inside a fixture, never while
this module is imported, so test collection is the same in every worker
and only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from benchmarks.common import serve_cfg
from repro.core import estimator as E
from repro.kernels import adc, hamming, l2dist, lsh_hash

N = 65536          # rows per kernel call
Q = 64             # queries of the batched ADC kernels
CAPACITY = 2 ** 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _kernel_in_program(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kc", [64, 256])
@pytest.mark.parametrize("name,lut_dtype,batched", [
    ("adc", jnp.float32, False), ("adc_batch", jnp.float32, True),
    ("adc_q8", jnp.uint8, False), ("adc_batch_q8", jnp.uint8, True)])
def test_adc_kernels_lower(one_chip, name, lut_dtype, batched, kc):
    m = 16
    codes = jax.ShapeDtypeStruct((N, m), jnp.uint8, sharding=one_chip)
    lut = jax.ShapeDtypeStruct((Q, m, kc) if batched else (m, kc), lut_dtype,
                               sharding=one_chip)
    fn = getattr(adc, name)
    _kernel_in_program(_compile(lambda c, t: fn(c, t, bn=512), codes, lut))


def test_hamming_lowers(one_chip):
    k = 12
    codes = jax.ShapeDtypeStruct((N, k), jnp.int32, sharding=one_chip)
    qcode = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=one_chip)
    _kernel_in_program(_compile(hamming.hamming, codes, qcode))


def test_lsh_hash_lowers(one_chip):
    d, f = 128, 12
    s = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
         for shape in ((N, d), (d, f), (f,), (f,))]
    _kernel_in_program(_compile(lsh_hash.lsh_hash, *s))


def test_l2dist_lowers(one_chip):
    x = jax.ShapeDtypeStruct((N, 128), jnp.float32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((Q, 128), jnp.float32, sharding=one_chip)
    _kernel_in_program(_compile(l2dist.l2dist, x, q))


def test_served_flush_fits_one_chip(one_chip):
    """The flush step chip_smoke.py serves — estimate_batch_stats under
    serve_cfg(128) at capacity 2^20 and its flush size — compiles for one
    chip. The compiler raises when the program exceeds the chip's HBM."""
    cfg = serve_cfg(128)
    state = jax.eval_shape(
        lambda x, k: E.attach_epochs(E.build(x, cfg, k, capacity=CAPACITY)),
        jax.ShapeDtypeStruct((1_000_000, 128), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                           sharding=one_chip)
    state = jax.tree_util.tree_map(place, state)
    q = chip_smoke.FLUSH
    args = (state, place(jax.ShapeDtypeStruct((q, 128), jnp.float32)),
            place(jax.ShapeDtypeStruct((q,), jnp.float32)))
    key = place(jax.ShapeDtypeStruct((2,), jnp.uint32))
    for step in (E.estimate_batch, E.estimate_batch_stats):
        compiled = step.lower(*args, cfg, key).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30
