"""Compile checks for one described TPU v5e chip — no chip attached.

The chip's own compiler lowers each Pallas kernel and the served estimate
step at serving widths, which interpret-mode runs on the CPU cannot
check: a kernel that the TPU lowering refuses (an unsupported primitive, a
misaligned block, too much VMEM) or a step that does not fit the chip's
HBM fails here. The topology is described inside a fixture, never while
this module is imported, so test collection is the same in every worker
and only the worker that runs this file loads the TPU compiler.
"""
import json
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from benchmarks.common import serve_cfg
from repro.core import estimator as E, pq
from repro.core.config import ProberConfig
from repro.kernels import adc, hamming, l2dist, lsh_hash

N = 65536          # rows per kernel call
Q = 64             # queries of the batched ADC kernels
CAPACITY = 2 ** 20
GIST = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / \
    "chip" / "configs" / "gist1m.json"


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


@pytest.mark.parametrize("check_ingest", [False, True])
def test_cached_flush_steps_compile_for_one_chip(one_chip, check_ingest):
    """The coalescer's lookup and write-back steps, as the benchmark's
    sift1m cell serves them (capacity 2^20, a 1024-entry cache, flushes
    of 8), compile for one chip, before and after the first ingest."""
    from repro.cache import estimate_cache as C
    from repro.serve import engine
    cfg = serve_cfg(128)
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                           sharding=one_chip)
    state = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda x, k: E.attach_epochs(E.build(x, cfg, k, capacity=CAPACITY)),
        jax.ShapeDtypeStruct((1_000_000, 128), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    cache = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda: C.init_cache(1024, cfg.n_tables, cfg.n_funcs)))
    idx, q = state.index, chip_smoke.FLUSH
    shape = lambda s, dt: place(jax.ShapeDtypeStruct(s, dt))  # noqa: E731
    index_args = (idx.bucket_codes, idx.bucket_sizes, idx.n_buckets)
    lookup = engine._lookup_step.lower(
        cache, state.epochs, idx.params, *index_args,
        shape((q, 128), jnp.float32), shape((q,), jnp.float32),
        shape((), jnp.int32), n_tables=cfg.n_tables, reuse_tol=0.0,
        match_qhash=True, check_ingest=check_ingest).compile()
    _, keys, _ = jax.eval_shape(
        lambda *a: engine._lookup_step(
            *a, n_tables=cfg.n_tables, reuse_tol=0.0, match_qhash=True,
            check_ingest=check_ingest),
        cache, state.epochs, idx.params, *index_args,
        shape((q, 128), jnp.float32), shape((q,), jnp.float32),
        shape((), jnp.int32))
    insert = engine._insert_step.lower(
        cache, state.epochs, *index_args,
        jax.tree_util.tree_map(place, keys), shape((q,), jnp.int32),
        shape((), jnp.int32), shape((q,), jnp.float32),
        shape((q, cfg.n_tables), jnp.int32), shape((q,), jnp.int32),
        match_qhash=True).compile()
    for compiled in (lookup, insert):
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def _gist_cfg() -> ProberConfig:
    """The prober settings the gist1m benchmark cell serves."""
    return ProberConfig(**json.loads(GIST.read_text())["prober"])


def test_gist_pq_build_fits_one_chip(one_chip):
    """The PQ build of a 1,000,000 x 960 corpus (M = 32), as
    ``E.build(capacity=2^20)`` runs it on the stored corpus, reads row
    blocks in place: k-means and encoding each hold at most 2 GiB of
    temporaries, where a padded copy of the corpus made the build ask
    14.9 GB."""
    x = jax.ShapeDtypeStruct((CAPACITY, E.stored_width(960)), jnp.float32,
                             sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    cfg = _gist_cfg()
    cents = jax.ShapeDtypeStruct((32, cfg.pq_kc, 30), jnp.float32,
                                 sharding=one_chip)
    for compiled in (
            pq.kmeans.lower(x, cfg, key, n_valid=1_000_000,
                            dim=960).compile(),
            pq.encode.lower(x, cents, cfg, n_valid=1_000_000).compile()):
        assert compiled.memory_analysis().temp_size_in_bytes <= 2 * 2 ** 30


def test_gist_flush_reads_the_corpus_in_place(one_chip):
    """The gist1m cell's flush (estimate_batch_stats, exact central
    bucket, capacity 2^20, 8 queries) on the corpus stored 1,024 wide:
    the probe's row gathers read it as laid out, so the program holds no
    copy of a corpus-sized operand and at most 2 GiB of temporaries."""
    cfg = _gist_cfg()
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                           sharding=one_chip)
    state = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda x, k: E.attach_epochs(E.build(x, cfg, k, capacity=CAPACITY)),
        jax.ShapeDtypeStruct((1_000_000, 960), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    assert state.x.shape == (CAPACITY, 1024)
    q = chip_smoke.FLUSH
    compiled = E.estimate_batch_stats.lower(
        state, place(jax.ShapeDtypeStruct((q, 960), jnp.float32)),
        place(jax.ShapeDtypeStruct((q,), jnp.float32)), cfg,
        place(jax.ShapeDtypeStruct((2,), jnp.uint32))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * 2 ** 30
    corpus_copy = re.compile(rf"= f32\[{CAPACITY},\d+\]\S* copy\(")
    assert not corpus_copy.search(compiled.as_text())
