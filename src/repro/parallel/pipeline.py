"""Pipeline parallelism (GPipe-style) via shard_map + lax.ppermute.

The layer stack is split into |model| contiguous stages (the stacked (L,...)
param leaves shard over 'model' on their layer dim — no weight reshuffling);
microbatches flow stage-to-stage through collective-permutes.  With the
production mesh this realizes PP=16 x DP=16 (TP=1) — the right regime for
mid-size dense models whose TP collectives dominate (yi/granite train cells,
see EXPERIMENTS.md §Roofline), trading them for the pipeline bubble
(S-1)/(S-1+n_micro).

Schedule: classic GPipe fill-drain over T = n_micro + S - 1 ticks.  At tick
t, stage 0 ingests microbatch t (if any); every stage applies its layers;
activations ppermute to the next stage; the last stage emits microbatch
t-S+1.  Differentiable end-to-end (grads flow back through ppermute), so
``pipelined_loss_fn`` drops into the standard train step.

Embedding runs on stage 0 and the LM head on the last stage (weights
replicated across stages for simplicity; a production variant would place
them).  Shapes: n_micro must be >= 1; batch shards over ('pod','data').
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.parallel.sharding import current_mesh


def _stage_apply(blocks, h, cfg, positions):
    """Apply this stage's layer slice (scan over local layers)."""

    def body(carry, blk):
        hh = carry
        a, _ = L.attention(
            blk["attn"], L.rmsnorm(blk["ln1"], hh, cfg.norm_eps), cfg,
            positions=positions,
        )
        hh = hh + a
        hh = hh + L.mlp(blk["mlp"], L.rmsnorm(blk["ln2"], hh, cfg.norm_eps), cfg)
        return hh, None

    fn = body
    if cfg.remat == "full":
        fn = jax.checkpoint(body, prevent_cse=False)
    h, _ = jax.lax.scan(fn, h, blocks, unroll=cfg.scan_unroll)
    return h


def pipelined_loss_fn(params, batch, cfg, *, n_micro: int, axis: str = "model"):
    """Cross-entropy loss of a dense decoder-only LM under PP over ``axis``.

    batch = {"tokens": (B, S+1)}.  Must run under an active mesh whose
    ``axis`` size divides cfg.n_layers.  Returns (loss, metrics).
    """
    mesh = current_mesh()
    assert mesh is not None, "pipelined_loss_fn requires an active mesh"
    n_stages = mesh.shape[axis]
    assert cfg.n_layers % n_stages == 0
    dpa = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = dpa if len(dpa) > 1 else dpa[0]

    tokens_all = batch["tokens"][:, :-1]
    targets_all = batch["tokens"][:, 1:]
    b, s = tokens_all.shape
    assert b % n_micro == 0
    mb = b // n_micro
    tok_mb = tokens_all.reshape(n_micro, mb, s)
    tgt_mb = targets_all.reshape(n_micro, mb, s)

    def body(blocks, embed, ln_f, head, toks, tgts):
        from repro.parallel import sharding as shd

        # inside shard_map every mesh axis is manual: the model's GSPMD
        # sharding constraints must no-op (shard_map owns the layout here)
        ctx = shd.use_mesh(None)
        ctx.__enter__()
        stage = jax.lax.axis_index(axis)
        last = n_stages - 1
        positions = jnp.arange(s)[None, :]
        mb_loc = toks.shape[1]

        h = jnp.zeros((mb_loc, s, cfg.d_model), jnp.bfloat16)
        loss_sum = jnp.float32(0)
        n_out = 0
        for t in range(n_micro + n_stages - 1):
            # stage 0 ingests microbatch t
            if t < n_micro:
                fresh = L.embed(embed, toks[t])
                h = jnp.where(stage == 0, fresh, h)
            h = _stage_apply(blocks, h, cfg, positions)
            # last stage emits microbatch t-(S-1)
            mi = t - (n_stages - 1)
            if 0 <= mi < n_micro:
                x = L.rmsnorm(ln_f, h, cfg.norm_eps)
                logits = L.linear(head, x, cfg.quant).astype(jnp.float32)
                logz = jax.scipy.special.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(
                    logits, tgts[mi][..., None], axis=-1)[..., 0]
                nll = (logz - gold).mean()
                loss_sum = loss_sum + jnp.where(stage == last, nll, 0.0)
                n_out += 1
            # ppermute activations stage i -> i+1 (ring; stage0's recv is
            # overwritten by the next ingest)
            h = jax.lax.ppermute(
                h, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
        # share the last stage's loss with every stage (grad flows back
        # through psum's transpose correctly: each stage contributed 0 or nll)
        loss = jax.lax.psum(loss_sum, axis) / n_out
        # batch-mean across DP shards
        for a in (dpa if isinstance(dp, tuple) else (dp,)):
            loss = jax.lax.pmean(loss, a)
        ctx.__exit__(None, None, None)
        return loss

    blocks_spec = jax.tree.map(lambda _: P(axis), params["blocks"])
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(blocks_spec, P(), P(), P(),
                  P(None, dp, None), P(None, dp, None)),
        out_specs=P(),
        check_vma=False,
    )
    loss = fn(params["blocks"], params["embed"], params["ln_f"],
              params["head"], tok_mb, tgt_mb)
    return loss, {"nll": loss}


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead: (S-1) / (S-1+M)."""
    return (n_stages - 1) / (n_stages - 1 + n_micro)
