"""End-to-end launcher: train or serve any registered architecture.

GNN archs (the paper's setting) train full-graph with Sylvie quantized halo
exchange; LM archs train on the synthetic token stream or serve batched
decode; DLRM trains on the synthetic Criteo stream.

``--scenario`` switches to the matrix runner (``launch/scenarios.py``): the
named arch x dataset x policy x runtime sweep runs end-to-end and writes one
report JSON per cell under ``artifacts/scenarios/<name>/``.

Examples (CPU-sized; production meshes via launch/dryrun.py):
    python -m repro.launch.train --arch gcn --mode sync --bits 1 --epochs 50
    python -m repro.launch.train --arch gcn --graph reddit_like@small --parts 8
    python -m repro.launch.train --arch olmoe-1b-7b --reduced --steps 50
    python -m repro.launch.train --arch dlrm-mlperf --reduced --steps 100
    python -m repro.launch.train --scenario smoke
    python -m repro.launch.train --scenario paper --only amazon_like
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from .cache import use_compile_cache


def build_policy(args):
    """CLI -> CommPolicy. ``--eps-s`` maps onto the BoundedStaleness policy
    (the old trainer kwarg survives only as a deprecation shim)."""
    from .. import policy as P

    if args.eps_s is not None and args.policy not in ("uniform",
                                                      "bounded_staleness"):
        raise SystemExit(f"--eps-s conflicts with --policy {args.policy}; "
                         "it implies bounded_staleness")
    if args.policy == "warmup":
        return P.Warmup(epochs=args.warmup_epochs, bits=args.bits)
    if args.policy == "adaqp":
        return P.AdaQPVariance(budget_bits=args.bits)
    if args.policy == "bounded_staleness" or args.eps_s is not None:
        if args.eps_s is None:
            raise SystemExit("--policy bounded_staleness needs --eps-s N "
                             "(the cache-refresh period)")
        return P.BoundedStaleness(eps_s=args.eps_s, bits=args.bits)
    return None  # Uniform from the SylvieConfig


def build_gnn_trainer(arch, graph: str, parts: int, cfg, *, policy=None,
                      seed: int = 0, runtime=None, ckpt_dir=None):
    """Generate ``graph`` (a named workload ref or raw generator name) from
    ``seed``, partition it ``parts`` ways and wrap ``arch`` in a
    :class:`~repro.train.trainer.GNNTrainer` (simulated runtime by default)."""
    from ..graph import formats, partition, synthetic
    from ..models.gnn import blocks as B
    from ..train.trainer import GNNTrainer

    from .. import datasets

    if graph in synthetic.GENERATORS:          # raw generator, default kwargs
        g = synthetic.by_name(graph, seed=seed)
    else:                              # named workload ("reddit_like@small");
        # a typo raises the registry's KeyError listing the known names/tiers
        g = datasets.load(graph, seed=seed)
    g, ew = formats.gcn_normalize(g)
    if arch.d_edge_attr:
        if g.pos is None:
            rng = np.random.default_rng(0)
            g.pos = rng.normal(0, 1, (g.n_nodes, 3)).astype(np.float32)
        g.edge_attr = B.geometry_edge_attr(g)
    pg = partition.partition_graph(g, parts, edge_weight=ew)
    model = arch.make(g.x.shape[1], g.n_classes)
    return GNNTrainer(model, pg, cfg, policy=policy, seed=seed,
                      runtime=runtime, ckpt_dir=ckpt_dir)


def train_gnn(args) -> None:
    from .. import configs as configlib
    from ..core.sylvie import SylvieConfig

    spec = configlib.get(args.arch)
    arch = spec.reduced() if args.reduced else spec.config()
    cfg = SylvieConfig(mode=args.mode, bits=args.bits,
                       schedule=args.schedule or "blocking")
    tr = build_gnn_trainer(arch, args.graph, args.parts, cfg,
                           policy=build_policy(args), seed=args.seed,
                           ckpt_dir=args.ckpt_dir)
    if args.resume and tr.resume():
        print(f"resumed at epoch {tr.epoch}")
    t0 = time.time()
    for _ in range(args.epochs):
        m = tr.train_epoch()
        if tr.epoch % args.log_every == 0:
            acc = tr.evaluate("val")
            print(f"epoch {m.epoch:4d} [{m.mode}] loss {m.loss:.4f} "
                  f"val {acc:.4f} comm {m.comm_payload_mb:.2f}MB "
                  f"(+{m.comm_ec_mb:.2f}MB ec) {m.seconds*1e3:.1f}ms")
    print(f"test acc {tr.evaluate('test'):.4f}  "
          f"({args.epochs} epochs in {time.time()-t0:.1f}s)")
    if args.ckpt_dir:
        tr.save()


def train_lm(args) -> None:
    from .. import configs as configlib
    from ..data.pipeline import Prefetcher, token_stream
    from ..models.lm import model as LM
    from ..train import optimizer as optlib

    spec = configlib.get(args.arch)
    cfg = spec.reduced() if args.reduced else spec.config()
    opt = optlib.adam(args.lr)
    key = jax.random.PRNGKey(args.seed)
    params = LM.init_params(key, cfg, dtype=jnp.float32)
    state = (params, opt.init(params), jnp.zeros((), jnp.int32))
    step_fn = jax.jit(LM.make_train_step(cfg, opt))
    stream = Prefetcher(token_stream(cfg.vocab, args.batch, args.seq,
                                     args.seed, n_batches=args.steps))
    t0 = time.time()
    for i, (tok, lab) in enumerate(stream):
        state, loss = step_fn(state, tok, lab)
        if (i + 1) % args.log_every == 0:
            print(f"step {i+1:5d} loss {float(loss):.4f} "
                  f"({(i+1)*args.batch*args.seq/(time.time()-t0):.0f} tok/s)")
    print(f"final loss {float(loss):.4f}")


def serve_lm(args) -> None:
    from .. import configs as configlib
    from ..models.lm import model as LM

    spec = configlib.get(args.arch)
    cfg = spec.reduced() if args.reduced else spec.config()
    key = jax.random.PRNGKey(args.seed)
    params = LM.init_params(key, cfg, dtype=jnp.float32)
    b, s_ctx, new = args.batch, args.seq, args.decode_tokens
    prefill = jax.jit(LM.make_prefill_step(cfg, b, s_ctx + new))
    decode = jax.jit(LM.make_decode_step(cfg))
    prompts = jax.random.randint(key, (b, s_ctx), 0, cfg.vocab)
    pad = jnp.zeros((b, new), jnp.int32)
    last, caches = prefill(params, jnp.concatenate([prompts, pad], 1)[:, :s_ctx + new][:, :s_ctx + new])
    # NB: prefill cache is sized for the full horizon; positions >= s_ctx are
    # masked by kv_len during decode.
    tok = jnp.argmax(last, -1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(new - 1):
        lg, caches = decode(params, caches, tok,
                            jnp.asarray(s_ctx + i, jnp.int32))
        tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        out.append(tok)
    dt = time.time() - t0
    print(f"decoded {b}x{new} tokens, {b*(new-1)/dt:.1f} tok/s")
    print("sample:", np.asarray(jnp.concatenate(out, 1))[0][:16])


def train_dlrm(args) -> None:
    from .. import configs as configlib
    from ..data.pipeline import Prefetcher, criteo_stream
    from ..models.recsys import dlrm as D
    from ..train import optimizer as optlib

    spec = configlib.get(args.arch)
    cfg = spec.reduced() if args.reduced else spec.config()
    opt = optlib.adam(args.lr)
    key = jax.random.PRNGKey(args.seed)
    dp = D.init_dense_params(key, cfg)
    tb = D.init_table(jax.random.fold_in(key, 1), cfg, n_dev=1)
    state = (dp, tb, opt.init(dp), opt.init(tb), jnp.zeros((), jnp.int32))
    step = jax.jit(D.make_train_step(cfg, opt, None))
    stream = Prefetcher(criteo_stream(cfg, args.batch, args.seed,
                                      n_batches=args.steps))
    for i, (dense, ids, label) in enumerate(stream):
        state, loss = step(state, dense, ids, label,
                           jax.random.fold_in(key, i))
        if (i + 1) % args.log_every == 0:
            print(f"step {i+1:5d} loss {float(loss):.4f}")
    print(f"final loss {float(loss):.4f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture id (required unless --scenario)")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-sized)")
    ap.add_argument("--serve", action="store_true",
                    help="LM: batched prefill+decode instead of training")
    # scenario-matrix runner (repro.launch.scenarios)
    ap.add_argument("--scenario", default=None,
                    help="run a named arch x dataset x policy x runtime "
                         "matrix end-to-end (smoke | policies | paper); "
                         "writes artifacts/scenarios/<name>/*.json")
    ap.add_argument("--only", default=None,
                    help="with --scenario: substring filter over cell ids")
    ap.add_argument("--scenario-dir", default=None,
                    help="with --scenario: report directory override")
    ap.add_argument("--obs", action="store_true",
                    help="with --scenario: arm span tracing per cell and "
                         "write artifacts/obs/<name>/<cell>.{trace,metrics}"
                         ".json (render: python -m repro.obs summarize)")
    ap.add_argument("--obs-dir", default=None,
                    help="with --scenario --obs: obs artifact directory "
                         "override")
    # GNN
    ap.add_argument("--graph", default="planted",
                    help="named workload ref ('reddit_like@small', see "
                         "repro.datasets.names()) or raw generator name "
                         "(planted | powerlaw | powerlaw_community | grid | "
                         "molecule)")
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--mode", default="sync",
                    choices=["vanilla", "sync", "async"])
    ap.add_argument("--bits", type=int, default=1)
    ap.add_argument("--schedule", default=None,
                    choices=["blocking", "overlap"],
                    help="halo-exchange schedule: blocking, or the fenced "
                         "issue/land overlap pipeline (dist/overlap.py; "
                         "bit-exact under sync). With --scenario, overrides "
                         "the scenario's schedule for every cell")
    ap.add_argument("--policy", default="uniform",
                    choices=["uniform", "warmup", "bounded_staleness",
                             "adaqp"],
                    help="per-epoch communication schedule (repro.policy); "
                         "adaqp treats --bits as the budget")
    ap.add_argument("--warmup-epochs", type=int, default=5)
    ap.add_argument("--eps-s", type=int, default=None,
                    help="cache-refresh period (implies bounded_staleness)")
    ap.add_argument("--epochs", type=int, default=50)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    # LM / DLRM
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--decode-tokens", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    if args.scenario:
        from .scenarios import run_scenario
        run_scenario(args.scenario, only=args.only,
                     out_dir=args.scenario_dir, schedule=args.schedule,
                     obs_trace=args.obs, obs_dir=args.obs_dir)
        return
    if args.arch is None:
        ap.error("--arch is required (or pass --scenario)")

    from .. import configs as configlib
    kind = configlib.get(args.arch).kind
    if kind == "gnn":
        train_gnn(args)
    elif kind == "lm":
        serve_lm(args) if args.serve else train_lm(args)
    else:
        train_dlrm(args)


if __name__ == "__main__":
    main()
