"""GPipe-style pipeline parallelism over a mesh axis (default: "model").

The stacked layer parameters [L, ...] are regrouped stage-major
[S, L/S, ...] and the stage dimension is sharded over the pipeline axis;
activations flow stage-to-stage with ``lax.ppermute`` inside a
``shard_map`` that is *manual* on the pipeline axis and *auto* (GSPMD) on
the data axes.  The schedule is the classic GPipe ramp: M microbatches
over M + S - 1 ticks; each device holds exactly one activation buffer, so
pipeline memory is O(1) buffers + saved residuals for AD (``jax.grad``
differentiates straight through the ppermute pipeline — its transpose is
the reverse permute, yielding the textbook backward ramp for free).

Trade vs tensor parallelism on the same axis: per-layer all-reduces
(2 * B*S*d bytes each) become one B*S*d ppermute per *stage boundary* —
~2L/S fewer bytes — at the price of the (S-1)/(M+S-1) bubble, which shows
up in the compute term instead of the collective term.  EXPERIMENTS.md
§Perf quantifies it on llama3-405b.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import transformer as tf
from repro.models.layers import chunked_xent, norm


def _regroup(layers, n_stages: int):
    """[L, ...] -> [S, L/S, ...] (stage-major)."""
    def r(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])
    return jax.tree.map(r, layers)


def make_pp_loss(cfg: ModelConfig, mesh, *, n_stages: int, n_micro: int,
                 axis: str = "model", remat: str = "full",
                 xent_chunk: int = 512, impl: str = "blockwise"):
    """Returns loss_fn(params, batch) running the backbone as a pipeline.

    Only the layer stack is pipelined; embedding / final norm / unembedding
    run replicated over the pipe axis (they are shared pre/post stages).
    Supports the decoder-only families (dense/moe/ssm/hybrid).
    """
    assert cfg.n_layers % n_stages == 0

    def stage_body(x, stage_layers, positions):
        def body(carry, lp):
            out = tf._layer_body(cfg, carry, lp, positions=positions,
                                 causal=True, impl=impl)
            return out, None
        b = jax.checkpoint(body) if remat in ("full", "block") else body
        x, _ = jax.lax.scan(b, x, stage_layers)
        return x

    def pipeline(stage_layers, x_mb, positions):
        """shard_map body — manual on `axis`.

        stage_layers: this stage's [L/S, ...] slice (leading stage dim
        already consumed by sharding); x_mb: [M, Bm, S, d] microbatches
        (same on every stage; only stage 0 reads them).
        """
        stage = jax.lax.axis_index(axis)
        # sharding leaves a size-1 stage dim on the local slice: squeeze it
        stage_layers = jax.tree.map(lambda a: a[0], stage_layers)
        S = n_stages
        M = n_micro
        fwd_perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            buf = carry                           # [Bm, S, d] (f32 boundary)
            # stage 0 injects microbatch t (if any); others take the
            # activation handed over from the previous stage
            inj = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
            x_in = jnp.where(stage == 0, inj, buf)
            y = stage_body(x_in.astype(jnp.bfloat16), stage_layers,
                           positions).astype(jnp.float32)
            # emit the last stage's finished microbatch, pass the rest on
            handed = jax.lax.ppermute(y, axis, fwd_perm)
            return handed, y

        buf0 = jnp.zeros_like(x_mb[0])
        _, ys = jax.lax.scan(tick, buf0, jnp.arange(M + S - 1))
        # microbatch m finishes on the last stage at tick m + S - 1
        out = jax.lax.dynamic_slice_in_dim(ys, S - 1, M, axis=0)
        # replicate the last stage's result across the pipe axis so the
        # shared loss epilogue (replicated out_specs) sees it everywhere.
        # All shard_map boundary dtypes stay f32: XLA:CPU's
        # AllReducePromotion pass crashes on the bf16 collectives that
        # bf16 boundaries would induce (fwd AND transposed bwd).
        mask = jnp.where(stage == S - 1, jnp.float32(1), jnp.float32(0))
        return jax.lax.psum(out * mask, axis)

    pp = jax.shard_map(
        pipeline, mesh=mesh, axis_names={axis},
        in_specs=(P(axis), P(), P()),
        out_specs=P(),
        check_vma=False)

    def loss_fn(params, batch):
        emb = params["embed"]
        tokens = batch["tokens"]
        B, Sq = tokens.shape
        assert B % n_micro == 0
        x = emb[tokens].astype(jnp.float32)
        positions = jnp.arange(Sq)
        x_mb = x.reshape(n_micro, B // n_micro, Sq, -1)
        staged = _regroup(params["layers"], n_stages)
        out = pp(staged, x_mb, positions)          # [M, Bm, S, d] f32
        h = out.reshape(B, Sq, -1).astype(jnp.bfloat16)
        h = norm(h, params["ln_f"], cfg.norm)
        unemb = params.get("unembed", emb)

        def logits_fn(hc, e):
            logits = jnp.einsum("bsd,vd->bsv", hc, e)
            if cfg.vocab_padded != cfg.vocab:
                mask = jnp.arange(cfg.vocab_padded) < cfg.vocab
                logits = jnp.where(mask, logits, -1e30)
            return logits

        return chunked_xent(logits_fn, h, unemb, batch["labels"],
                            chunk=xent_chunk)

    return loss_fn
