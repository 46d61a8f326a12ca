"""Serving launcher (DESIGN.md §12): the thin CLI over the production
serving engine — paged KV cache, continuous batching, optional
multi-replica routing — with the classic one-shot batched generate kept
as a mode (and as the bit-identity reference).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b \
        --batch 4 --prompt-len 32 --gen 16 --engine continuous

    # serving trace: Poisson arrivals, 2 replicas, placement plan
    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b \
        --requests 16 --rate 50 --replicas 2 --plan --topology two_tier_pod
"""
from __future__ import annotations

import argparse
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_ARCHS, get_config, reduced
from repro.launch.paths import use_compile_cache
from repro.models import Model


class GenerateSession:
    """Holds the jitted prefill/decode programs for one model so repeated
    ``generate`` calls never recompile (they used to build fresh ``jax.jit``
    wrappers per request)."""

    def __init__(self, model: Model):
        from repro.models.sharding_ctx import mesh_ctx
        self.model = model

        # Trace under a cleared activation-sharding context: the ctx is
        # process-global (set by the training launcher) and a leaked mesh
        # would bake sharding constraints into the serving programs (see
        # Engine._build_jits).
        def prefill_fn(params, batch, *, max_len):
            with mesh_ctx(None, ()):
                return model.prefill(params, batch, max_len=max_len)

        def decode_fn(params, tok, cache, pos):
            with mesh_ctx(None, ()):
                return model.decode_step(params, tok, cache, pos)

        self._prefill = jax.jit(prefill_fn, static_argnames=("max_len",))
        self._decode = jax.jit(decode_fn, donate_argnums=(2,))

    def compile_counts(self):
        return {"prefill": self._prefill._cache_size(),
                "decode": self._decode._cache_size()}

    def generate(self, params, prompts, gen: int, max_len: int, rng,
                 src=None, temperature: float = 0.0):
        """prompts: (B, P) int32. Returns (B, gen) sampled tokens."""
        B, Plen = prompts.shape
        batch = {"tokens": prompts}
        if src is not None:
            batch["src"] = src
        logits, cache = self._prefill(params, batch, max_len=max_len)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out = [tok]
        for i in range(gen - 1):
            logits, cache = self._decode(params, tok, cache,
                                         jnp.asarray(Plen + i, jnp.int32))
            if temperature > 0:
                rng, k = jax.random.split(rng)
                tok = jax.random.categorical(k, logits[:, -1] / temperature)
                tok = tok[:, None].astype(jnp.int32)
            else:
                tok = jnp.argmax(logits[:, -1],
                                 axis=-1).astype(jnp.int32)[:, None]
            out.append(tok)
        return jnp.concatenate(out, axis=1)


_SESSIONS: "weakref.WeakKeyDictionary[Model, GenerateSession]" = \
    weakref.WeakKeyDictionary()


def session_for(model: Model) -> GenerateSession:
    s = _SESSIONS.get(model)
    if s is None:
        s = GenerateSession(model)
        _SESSIONS[model] = s
    return s


def generate(model: Model, params, prompts, gen: int, max_len: int, rng,
             src=None, temperature: float = 0.0):
    """prompts: (B, P) int32. Returns (B, gen) sampled tokens.  Compiled
    programs are cached per model via :func:`session_for`."""
    return session_for(model).generate(params, prompts, gen, max_len, rng,
                                       src=src, temperature=temperature)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="serve a reduced config: continuous batching engine, "
                    "static batching, or one-shot generate")
    ap.add_argument("--arch", choices=ALL_ARCHS, default="gemma-2b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="CPU-runnable reduced config (--no-reduced for "
                         "the full one)")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode batch (engine slot count)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--engine",
                    choices=("continuous", "static", "oneshot"),
                    default="continuous")
    ap.add_argument("--requests", type=int, default=0,
                    help="trace length (default: --batch requests)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate, req/s (0 = all at t=0)")
    ap.add_argument("--max-len", type=int, default=0,
                    help="KV length per slot (default prompt+gen)")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--pages", type=int, default=0,
                    help="KV pool pages (0 = fully provisioned)")
    ap.add_argument("--quantize", choices=("none", "int8"), default="none",
                    help="int8 paged KV (lossy)")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan", action="store_true",
                    help="print the tp x tier serving placement search")
    ap.add_argument("--topology", default="two_tier_pod",
                    help="topology preset or spec for --plan")
    ap.add_argument("--latency-budget-ms", type=float, default=0.0)
    return ap


def _print_plan(cfg, args):
    from repro.core.schedule import (TOPOLOGY_PRESETS, Topology,
                                     plan_serving)
    from repro.launch.report import render_serving_plan
    from repro.models.model import count_params
    spec = TOPOLOGY_PRESETS.get(args.topology, args.topology)
    net = Topology.from_spec(spec)
    budget = (args.latency_budget_ms / 1e3
              if args.latency_budget_ms > 0 else None)
    best, arms = plan_serving(
        net, net.world, count_params(cfg) * 2.0, cfg.num_layers,
        cfg.d_model, batch=args.batch, latency_budget_s=budget)
    print(render_serving_plan(best, arms, arch=cfg.name, batch=args.batch,
                              latency_budget_s=budget))
    return best


def main(argv=None):
    from repro.serve import (Engine, MultiReplicaServer, Request,
                             ServeConfig, run_static)
    from repro.serve.engine import latency_summary, poisson_trace

    args = build_parser().parse_args(argv)
    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.plan:
        _print_plan(cfg, args)
    model = Model(cfg)
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)
    max_len = args.max_len or (args.prompt_len + args.gen)
    if args.engine == "continuous":
        # pages tile the slot exactly: round the KV length up to a page
        max_len = -(-max_len // args.page_size) * args.page_size
    n_req = args.requests or args.batch
    engine_kind = args.engine
    src = None
    if cfg.embedding_inputs:
        # encoder-decoder: no paged decode path — one-shot reference only
        engine_kind = "oneshot"
        src = jax.random.normal(rng, (args.batch, args.prompt_len,
                                      cfg.d_model))

    t0 = time.time()
    if engine_kind == "oneshot":
        prompts = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                     cfg.vocab_size)
        toks = generate(model, params, prompts, args.gen, max_len, rng,
                        src=src, temperature=args.temperature)
        dt = time.time() - t0
        print(f"arch={cfg.name} engine=oneshot generated {toks.shape} in "
              f"{dt:.2f}s ({args.batch * args.gen / dt:.1f} tok/s)")
        print("sample:", np.asarray(toks[0])[:16])
        assert np.isfinite(np.asarray(toks)).all()
        return toks

    if args.rate > 0:
        requests = poisson_trace(n_req, 1.0 / args.rate, args.prompt_len,
                                 [args.gen], cfg.vocab_size,
                                 seed=args.seed)
        for r in requests:
            r.temperature = args.temperature
    else:
        trng = np.random.default_rng(args.seed)
        requests = [Request(
            rid=i,
            prompt=trng.integers(0, cfg.vocab_size,
                                 size=(args.prompt_len,)).astype(np.int32),
            max_new=args.gen, arrival_s=0.0,
            temperature=args.temperature) for i in range(n_req)]

    if engine_kind == "static":
        comps = run_static(model, params, requests, args.batch, max_len)
    else:
        scfg = ServeConfig(
            max_batch=args.batch, max_len=max_len,
            page_size=args.page_size, n_pages=args.pages or None,
            quantize=None if args.quantize == "none" else args.quantize,
            seed=args.seed)
        if args.replicas > 1:
            srv = MultiReplicaServer(
                [Engine(model, params, scfg) for _ in range(args.replicas)])
            comps = srv.run(requests)
        else:
            comps = Engine(model, params, scfg).run(requests)
    dt = time.time() - t0
    s = latency_summary(comps)
    print(f"arch={cfg.name} engine={engine_kind} replicas={args.replicas} "
          f"requests={len(comps)} tokens={s['tokens']} in {dt:.2f}s")
    print(f"  tokens/s={s['tokens_per_s']:.1f} p50={s['p50_s'] * 1e3:.2f}ms "
          f"p99={s['p99_s'] * 1e3:.2f}ms "
          f"ttft={s['mean_ttft_s'] * 1e3:.2f}ms (trace time)")
    toks = np.stack([c.tokens for c in comps])
    print("sample:", toks[0][:16])
    assert np.isfinite(toks).all()
    return toks


if __name__ == "__main__":
    main()
