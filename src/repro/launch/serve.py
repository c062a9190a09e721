"""Serving driver: model + engine + CE-backed semantic planner behind one
CLI — the deployment shape of the paper's technique (DESIGN.md §2).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --scale smoke \
      --requests 8 --corpus 4000

Loads (or initializes) weights, builds the Dynamic Prober index over the
document-embedding corpus, then serves a stream of semantic operators:
estimate -> plan -> batched prefill/decode. On a pod the same driver lowers
full configs (proven by launch/dryrun.py); here it runs reduced configs for
real.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.config import ProberConfig
from repro.models import get_family
from repro.serve.engine import Request, ServeEngine
from repro.serve.semantic import SemanticPlanner
from repro.utils import compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCHS, default="qwen2-7b")
    ap.add_argument("--scale", choices=["smoke", "full"], default="smoke")
    ap.add_argument("--corpus", type=int, default=4000)
    ap.add_argument("--emb-dim", type=int, default=64)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-calls", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=0,
                    help="shard the estimator corpus over this many devices "
                         "(0 = single-device; needs that many jax devices, "
                         "e.g. via XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    ap.add_argument("--stopping", choices=["local", "sync"], default="local",
                    help="distributed stopping mode (DESIGN.md §4); only "
                         "meaningful with --shards > 1")
    args = ap.parse_args(argv)
    compile_cache.enable()

    key = jax.random.PRNGKey(args.seed)
    cfg = (configs.get_smoke_config(args.arch) if args.scale == "smoke"
           else configs.get_config(args.arch))
    assert cfg.family == "dense", "the engine drives dense-family models"
    fam = get_family(cfg)
    params = fam.init(key, cfg)
    engine = ServeEngine(cfg, params, batch_slots=args.slots,
                         max_len=args.max_len)

    corpus = jax.random.normal(key, (args.corpus, args.emb_dim))
    pcfg = ProberConfig(n_tables=2, n_funcs=8, ring_budget=1024,
                        central_budget=1024, chunk=128)
    mesh = None
    if args.shards > 1:
        assert args.corpus % args.shards == 0, \
            f"--shards {args.shards} must divide --corpus {args.corpus}"
        assert len(jax.devices()) >= args.shards, \
            f"--shards {args.shards} needs that many jax devices " \
            f"(have {len(jax.devices())}; set XLA_FLAGS=" \
            f"--xla_force_host_platform_device_count={args.shards})"
        mesh = jax.make_mesh((args.shards,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,),
                             devices=jax.devices()[:args.shards])
    planner = SemanticPlanner(corpus, pcfg, key, max_calls=args.max_calls,
                              slot_budget=args.slots, mesh=mesh,
                              mode=args.stopping)
    where = f"{args.shards}-shard/{args.stopping}" if mesh else "1-device"
    print(f"serving {cfg.name} ({args.scale}) | corpus={args.corpus} docs "
          f"| estimator {where}")

    rng = np.random.default_rng(args.seed)
    served = refused = 0
    t0 = time.time()
    for rid in range(args.requests):
        q = corpus[int(rng.integers(0, args.corpus))]
        d2 = jnp.sort(jnp.sum((corpus - q[None]) ** 2, axis=-1))
        target = int(rng.choice([2, 8, 32, args.max_calls * 4]))
        tau = float(jnp.sqrt(d2[min(target, args.corpus - 1)]))
        plan = planner.plan(q, tau)
        if plan.action != "execute":
            refused += 1
            print(f"req {rid}: est={plan.est_matches:8.1f} -> {plan.action} "
                  f"({plan.reason})")
            continue
        d2q = jnp.sum((corpus - q[None]) ** 2, axis=-1)
        matches = np.asarray(jnp.argsort(d2q)[: max(plan.llm_calls, 1)])
        for doc in matches:
            engine.submit(Request(rid=int(doc),
                                  prompt=rng.integers(2, cfg.vocab, size=8),
                                  max_new=4))
        done = engine.run()
        served += len(done)
        print(f"req {rid}: est={plan.est_matches:8.1f} -> {len(done)} LLM "
              f"calls ({plan.n_batches} batches x {plan.batch_slots} slots)")
    dt = time.time() - t0
    print(f"\n{served} LLM calls served, {refused} operators refused "
          f"by the planner, {dt:.1f}s total")
    return served, refused


if __name__ == "__main__":
    main()
