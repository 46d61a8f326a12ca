"""Tiny embedding+linear LM, duck-typed like ``repro.models.Model`` (has
``loss(params, batch)`` and ``loss_and_counts(params, batch)`` over
{'tokens': (B, T)}; no experts, so its MoE counters are 0): the
conformance suite's workhorse — big enough to fuse into multiple buckets, small enough that a
strategy × wire × mode sweep trains in seconds.  Shared by
test_conformance.py and the multi_device_checks.py subprocess.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.moe import no_aux


def _no_counts():
    z = jnp.zeros((), jnp.float32)
    return {"moe_dropped": z, "moe_routed": z}


class TinyLM:
    def __init__(self, vocab: int = 64, d: int = 16):
        self.vocab, self.d = vocab, d

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        return {"emb": jax.random.normal(k1, (self.vocab, self.d)) * 0.1,
                "out": jax.random.normal(k2, (self.d, self.vocab)) * 0.1,
                "b": jnp.zeros((self.vocab,))}

    def loss(self, params, batch):
        toks = batch["tokens"]
        x = params["emb"][toks[:, :-1]]
        logits = x @ params["out"] + params["b"]
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, toks[:, 1:][..., None], -1))

    def loss_and_counts(self, params, batch):
        return self.loss(params, batch), _no_counts()


def tiny_batch(step: int, batch: int = 8, seq: int = 16, vocab: int = 64):
    return {"tokens": jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(42), step),
        (batch, seq), 0, vocab)}


class TinyStackLM:
    """TinyLM with a homogeneous stack of residual MLP blocks — the
    pipeline conformance workhorse.  Exposes BOTH surfaces:

      * ``loss(params, batch)`` — the single-program reference path;
      * the staged surface ``make_pipeline_train_step`` consumes
        (``layout`` / ``split`` / ``merge`` / ``embed_mb`` /
        ``stage_apply`` / ``loss_tail`` / ``aux_coef``), with blocks
        stored stacked ``(R, ...)`` and cut into ``n_stages`` row groups.

    ``loss`` is by construction the composition
    ``loss_tail(shared, stage_apply(all rows, embed_mb(...)), tokens)`` so
    the S=1 pipeline step computes the same math.
    """

    def __init__(self, vocab: int = 64, d: int = 16, hidden: int = 32,
                 blocks: int = 4, n_stages: int = 1):
        from repro.core.pipeline import StageLayout
        if blocks % n_stages:
            raise ValueError((blocks, n_stages))
        self.vocab, self.d, self.hidden = vocab, d, hidden
        self.layout = StageLayout(n_stages=n_stages, rows=blocks,
                                  rows_per_stage=blocks // n_stages)
        self.aux_coef = 0.0

    def init(self, rng):
        ks = jax.random.split(rng, 4)
        R, d, h = self.layout.rows, self.d, self.hidden
        return {
            "emb": jax.random.normal(ks[0], (self.vocab, d)) * 0.1,
            "blocks": {
                "w1": jax.random.normal(ks[1], (R, d, h)) * 0.3,
                "b1": jnp.zeros((R, h)),
                "w2": jax.random.normal(ks[2], (R, h, d)) * 0.3,
            },
            "out": jax.random.normal(ks[3], (d, self.vocab)) * 0.1,
            "b": jnp.zeros((self.vocab,)),
        }

    # -- staged surface ------------------------------------------------------

    def split(self, params):
        S, rps = self.layout.n_stages, self.layout.rows_per_stage
        shared = {k: v for k, v in params.items() if k != "blocks"}
        rows = jax.tree.map(
            lambda x: x.reshape((S, rps) + x.shape[1:]), params["blocks"])
        return shared, rows

    def merge(self, shared, rows_stacked):
        R = self.layout.rows
        out = dict(shared)
        out["blocks"] = jax.tree.map(
            lambda x: x.reshape((R,) + x.shape[2:]), rows_stacked)
        return out

    def embed_mb(self, shared, tokens):
        return shared["emb"][tokens[:, :-1]]

    def stage_apply(self, rows, h):
        for i in range(self.layout.rows_per_stage):
            w1, b1, w2 = rows["w1"][i], rows["b1"][i], rows["w2"][i]
            # row-boundary barrier: keeps XLA fusion from crossing cut
            # points, so the rows' subgraphs (and their backward) compile
            # identically whether a ppermute sits between them or not —
            # the stage-count bit-exactness contract (DESIGN.md §9)
            h = jax.lax.optimization_barrier(
                h + jnp.tanh(h @ w1 + b1) @ w2)
        return h, no_aux()

    def loss_tail(self, shared, h, tokens):
        logits = h @ shared["out"] + shared["b"]
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(
            lp, tokens[:, 1:][..., None], -1))

    # -- reference single-program path --------------------------------------

    def loss(self, params, batch):
        shared, rows = self.split(params)
        rows = jax.tree.map(
            lambda x: x.reshape((self.layout.rows,) + x.shape[2:]), rows)
        h = self.embed_mb(shared, batch["tokens"])
        for i in range(self.layout.rows):
            w1, b1, w2 = rows["w1"][i], rows["b1"][i], rows["w2"][i]
            h = h + jnp.tanh(h @ w1 + b1) @ w2
        return self.loss_tail(shared, h, batch["tokens"])

    def loss_and_counts(self, params, batch):
        return self.loss(params, batch), _no_counts()
