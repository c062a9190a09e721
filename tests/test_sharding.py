"""Sharding-rule unit tests + 8-device CPU integration tests (subprocess so
the forced device count doesn't leak into other tests)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import specs as S


def _run(code: str, timeout: int = 480):
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True,
                          env={**os.environ, "PYTHONPATH": "src"},
                          cwd=os.path.dirname(os.path.dirname(__file__)),
                          timeout=timeout)


def test_cell_support_matrix():
    cfg_dense = configs.get_config("qwen2-7b")
    ok, why = S.cell_supported(cfg_dense, "long_500k")
    assert not ok and "sub-quadratic" in why
    for arch in ("rwkv6-1.6b", "recurrentgemma-9b"):
        ok, _ = S.cell_supported(configs.get_config(arch), "long_500k")
        assert ok
    for shape in ("train_4k", "prefill_32k", "decode_32k"):
        for arch in configs.ARCHS:
            ok, _ = S.cell_supported(configs.get_config(arch), shape)
            assert ok


def test_sharded_paths_on_trivial_mesh():
    """The whole distributed surface on a 1-device mesh (fast, in-process):
    build/update/estimate run, sync == local == the single-device batched
    path bit-for-bit (one shard pools only with itself)."""
    from repro.core import distributed as D, estimator as E
    from repro.core.config import ProberConfig
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2000, 16))
    cfg = ProberConfig(n_tables=2, n_funcs=6, ring_budget=512,
                       central_budget=512, chunk=128)
    state, params = D.build_sharded(x[:1000], cfg, key, mesh, capacity=4096)
    nv = None
    for i in range(1000, 2000, 250):
        state, nv = D.update_sharded(state, np.asarray(x[i:i + 250]), cfg,
                                     mesh, n_valid=nv)
    assert nv.tolist() == [2000]
    qs, taus = x[:4] + 0.01, jnp.linspace(3.0, 6.0, 4)
    got_l = D.estimate_sharded(state, qs, taus, cfg, key, mesh, mode="local")
    got_s = D.estimate_sharded(state, qs, taus, cfg, key, mesh, mode="sync")
    # reference: the local shard state through the plain batched path with
    # the same per-shard folded key
    st_local = jax.tree_util.tree_map(lambda a: a[0], state)
    want = E.estimate_batch(st_local, qs, taus, cfg,
                            jax.random.fold_in(key, 0))
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want))


def test_param_specs_divisibility_fallback():
    """whisper vocab 51865 %16 != 0 -> embedding replicated, never an error."""
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from jax.sharding import PartitionSpec as P
        from repro import configs
        from repro.launch import specs as S
        from repro.sharding import rules
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = configs.get_config("whisper-medium")
        params = S.param_specs_for(cfg)
        specs = rules.param_specs(params, mesh, "fsdp_tp")
        emb = specs["embed"]["embedding"]
        assert emb[0] is None, emb      # 51865 % 4 != 0 -> replicated
        cfg2 = configs.get_config("olmo-1b")
        specs2 = rules.param_specs(S.param_specs_for(cfg2), mesh, "fsdp_tp")
        assert specs2["embed"]["embedding"] == P("model", "data")
        wq = specs2["layers"]["attn"]["wq"]
        assert wq == P(None, "data", "model"), wq
        print("OK")
    """
    r = _run(code)
    assert "OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_8dev_train_step_parity():
    """The sharded train step must match single-device numerics."""
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import configs
        from repro.launch.train import build_trainer
        from repro.launch.mesh import make_host_mesh
        from repro.optim import adamw
        from repro.models import get_family
        from repro.train.step import make_train_step

        cfg = configs.get_smoke_config("olmo-1b")
        opt_cfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=10)
        fam = get_family(cfg)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)}
        batch["labels"] = batch["tokens"]

        # single-device reference: loss + grads (adam's step-1 update is
        # ~sign(g), ill-conditioned to reduction-order noise, so we compare
        # the gradients themselves)
        params = fam.init(jax.random.PRNGKey(0), cfg)
        loss_fn = lambda p, b: fam.loss_fn(p, b, cfg)
        l_ref, g_ref = jax.jit(jax.value_and_grad(loss_fn))(params, batch)

        # 8-device (2 data x 4 model); grads BEFORE the step (params donated)
        mesh = make_host_mesh(model=4)
        p, o, jitted = build_trainer(cfg, mesh, opt_cfg)
        l_sh, g_sh = jax.jit(jax.value_and_grad(loss_fn))(p, batch)
        p2, o2, m = jitted(p, o, batch)
        assert abs(float(m["loss"]) - float(l_ref)) < 1e-3, \\
            (float(m["loss"]), float(l_ref))
        gn_ref = adamw.global_norm(g_ref)
        gn_sh = adamw.global_norm(g_sh)
        assert abs(float(gn_ref) - float(gn_sh)) / float(gn_ref) < 2e-2
        w_ref = np.asarray(g_ref["layers"]["mlp"]["wi"])
        w_got = np.asarray(jax.device_get(g_sh["layers"]["mlp"]["wi"]))
        np.testing.assert_allclose(w_got, w_ref, rtol=0.1, atol=1e-2)
        print("OK parity")
    """
    r = _run(code)
    assert "OK parity" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


@pytest.mark.slow
def test_8dev_distributed_estimator():
    """psum'd sharded prober == additive over shards (exact-mode check),
    in BOTH stopping modes: with eps=0/s1=1 every ring is exhausted, so
    local and pooled-sync stopping must each recover the exact count."""
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.core.config import ProberConfig
        from repro.core import estimator as E, distributed as D
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (4000, 32))
        cfg = ProberConfig(n_tables=1, n_funcs=6, ring_budget=1024,
                           central_budget=1024, chunk=128, eps=0.0, s1=1.0,
                           max_visit=100000)
        state, params = D.build_sharded(x, cfg, key, mesh)
        qs = x[:3] + 0.01
        taus = jnp.array([1.0, 3.0, 6.0])
        for mode in ("local", "sync"):
            est = D.estimate_sharded(state, qs, taus, cfg, key, mesh,
                                     mode=mode)
            for i in range(3):
                truth = float(E.true_cardinality(x, qs[i], taus[i]))
                got = float(est[i])
                assert abs(got - truth) < 1e-2, (mode, i, got, truth)
        print("OK distributed")
    """
    r = _run(code)
    assert "OK distributed" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


@pytest.mark.slow
def test_8dev_sharded_ingest_recompile_free():
    """DESIGN.md §10 extended to the sharded index: after the first chunk
    compiles the shard_map ingest step, further in-capacity round-robin
    updates (and estimates between them) trigger ZERO new XLA compilations,
    per-shard live counts stay balanced, W stays globally consistent, and
    the post-ingest exact-mode estimate matches the ground truth."""
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax import monitoring
        from repro.core.config import ProberConfig
        from repro.core import estimator as E, distributed as D
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (4000, 32))
        cfg = ProberConfig(n_tables=1, n_funcs=6, ring_budget=1024,
                           central_budget=1024, chunk=128, eps=0.0, s1=1.0,
                           max_visit=100000)
        state, params = D.build_sharded(x[:2000], cfg, key, mesh,
                                        capacity=16384)
        qs = x[:3] + 0.01
        taus = jnp.array([1.0, 3.0, 6.0])
        # warm the ingest and estimate steps once
        state, nv = D.update_sharded(state, np.asarray(x[2000:2400]), cfg,
                                     mesh)
        D.estimate_sharded(state, qs, taus, cfg, key, mesh)
        events = []
        def cb(event, **kw):
            if "compile" in event:
                events.append(event)
        monitoring.register_event_listener(cb)
        state, nv = D.update_sharded(state, np.asarray(x[2400:2800]), cfg,
                                     mesh, n_valid=nv)
        state, nv = D.update_sharded(state, np.asarray(x[2800:3200]), cfg,
                                     mesh, n_valid=nv)
        est = D.estimate_sharded(state, qs, taus, cfg, key, mesh)
        monitoring.unregister_event_listener(cb)
        assert events == [], f"sharded in-capacity ingest recompiled: "\\
            f"{events}"
        assert nv.tolist() == [400] * 8, nv          # round-robin balance
        w = np.asarray(jax.device_get(state.index.params.w))
        assert np.allclose(w, w[0:1]), "per-shard W diverged"
        for i in range(3):
            truth = float(E.true_cardinality(x[:3200], qs[i], taus[i]))
            assert abs(float(est[i]) - truth) < 1e-2, (i, float(est[i]),
                                                       truth)
        print("OK sharded ingest")
    """
    r = _run(code)
    assert "OK sharded ingest" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


@pytest.mark.slow
def test_8dev_sync_beats_local_on_skewed_shards():
    """Pooled-stopping parity (DESIGN.md §4): on a skewed shard split —
    query-cluster mass on shard 0, sparse far-ring matches behind large
    unqualified near rings on shards 1-7 — local per-shard ε-stopping PTFs
    early and truncates the scattered matches, while the sync mode's pooled
    statistics keep the global selectivity above ε and keep probing. Sync
    mean q-error must be <= local mean q-error (fully seeded run)."""
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.config import ProberConfig
        from repro.core import estimator as E, distributed as D
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(0)
        d, S, n_shard, tau, n_sp = 16, 8, 1000, 3.0, 10
        def shell(n, r_lo, r_hi):
            v = rng.normal(size=(n, d))
            v /= np.linalg.norm(v, axis=1, keepdims=True)
            return (v * rng.uniform(r_lo, r_hi, size=(n, 1))
                    ).astype(np.float32)
        # shard 0: the query cluster; shards 1-7: a shell just outside tau
        # (big unqualified near rings) + n_sp true matches just inside tau
        # (they land in deeper rings)
        parts = [shell(n_shard, 0.0, tau * 1.05)]
        for s in range(1, S):
            parts.append(np.concatenate(
                [shell(n_shard - n_sp, tau * 1.05, tau * 1.35),
                 shell(n_sp, tau * 0.80, tau * 0.98)]))
        x = jnp.asarray(np.concatenate(parts))
        key = jax.random.PRNGKey(0)
        cfg = ProberConfig(n_tables=1, n_funcs=8, n_regions=4,
                           ring_budget=2048, central_budget=2048, chunk=64,
                           s1=0.05, eps=0.12)
        state, params = D.build_sharded(x, cfg, key, mesh)
        qs = jnp.asarray(np.tile(np.zeros(d, np.float32), (6, 1)) +
                         0.01 * rng.standard_normal((6, d)).astype(np.float32))
        taus = jnp.full((6,), tau)
        tr = np.asarray([float(E.true_cardinality(x, qs[i], taus[i]))
                         for i in range(6)])
        el = np.asarray(D.estimate_sharded(state, qs, taus, cfg, key, mesh,
                                           mode="local"))
        es = np.asarray(D.estimate_sharded(state, qs, taus, cfg, key, mesh,
                                           mode="sync"))
        def qe(e, t):
            e, t = max(e, 1.0), max(t, 1.0)
            return max(e / t, t / e)
        mq_l = np.mean([qe(el[i], tr[i]) for i in range(6)])
        mq_s = np.mean([qe(es[i], tr[i]) for i in range(6)])
        print(f"mq_local={mq_l:.4f} mq_sync={mq_s:.4f}")
        assert mq_s <= mq_l + 1e-6, (mq_s, mq_l)
        # and sync must actually be accurate, not just relatively better
        assert mq_s < 1.05, mq_s
        print("OK sync parity")
    """
    r = _run(code)
    assert "OK sync parity" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
