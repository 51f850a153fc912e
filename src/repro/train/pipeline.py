"""Pipeline parallelism (GPipe wavefront) + in-stage tensor parallelism.

Motivation (§Perf, nemotron-4-340b/train_4k): on a fixed (data=16, model=16)
mesh, 340B params cannot fit replicated (42.5 GB/chip) and FSDP re-gathers
every parameter every microbatch — ~23 TB of all-gather per device per step
(the measured baseline).  Pipelining makes weights STATIONARY:

  * "model" axis = 16 pipeline stages (n_layers/16 layers each);
  * "data" axis  = 16-way Megatron tensor parallelism inside each stage
    (q-heads/ff columns sharded; the 8 GQA KV heads are replicated — kv
    head r//2 serves device r's 6 query heads);
  * microbatches stream through a lax.scan wavefront; stage hand-off is a
    single seq-sharded ``collective_permute`` (residuals travel sharded:
    Megatron-SP all-gather(seq) → compute → reduce-scatter(seq) per block);
  * "pod" axis (multi-pod) = data parallelism over pipeline replicas.

The collective bill becomes activation-sized instead of parameter-sized:
per device ≈ L_loc·mb·4·|x|·(g−1)/g ≈ 0.9 TB vs 23 TB — the hypothesis→
measure log lives in EXPERIMENTS.md §Perf.

The backward pipeline is DERIVED: ``jax.grad`` through the ppermute/scan
forward yields the reverse wavefront automatically; ``jax.checkpoint`` on
the per-tick stage body keeps only seq-sharded carries alive.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.transformer import layers as L
from repro.models.transformer.config import TransformerConfig

Params = Dict[str, jnp.ndarray]


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    stage_axis: str = "model"
    tp_axis: str = "data"
    dp_axis: Optional[str] = "pod"      # absent on single-pod meshes
    microbatches: int = 16


# ---------------------------------------------------------------------------
# parameter layout
# ---------------------------------------------------------------------------

def param_pspecs(cfg: TransformerConfig, pcfg: PipelineConfig, mesh: Mesh
                 ) -> Dict[str, P]:
    """PartitionSpec per flat param name (layer dim → stages, heads/ff → TP,
    emb D-sharded, head V-sharded).  KV tensors replicate over TP."""
    st, tp = pcfg.stage_axis, pcfg.tp_axis
    specs = {
        "layers/attn_norm": P(st, None),
        "layers/mlp_norm": P(st, None),
        "layers/wq": P(st, None, tp, None),
        "layers/wk": P(st, None, None, None),
        "layers/wv": P(st, None, None, None),
        "layers/wo": P(st, tp, None, None),
        "layers/wi": P(st, None, tp),
        "layers/wi_gate": P(st, None, tp),
        "layers/wi_up": P(st, None, tp),
        "layers/wo_mlp": P(st, tp, None),
        "layers/bq": P(st, tp, None),
        "layers/bk": P(st, None, None),
        "layers/bv": P(st, None, None),
        "emb": P(None, tp),
        "head": P(None, tp),
        "final_norm": P(),
    }
    return specs


def validate(cfg: TransformerConfig, pcfg: PipelineConfig, mesh: Mesh):
    st = mesh.shape[pcfg.stage_axis]
    tp = mesh.shape[pcfg.tp_axis]
    assert cfg.n_layers % st == 0, "layers must divide stages"
    assert cfg.n_heads % tp == 0, "q heads must divide TP"
    assert cfg.d_ff % tp == 0, "d_ff must divide TP"
    assert cfg.d_model % tp == 0, "d_model must divide TP (emb shard)"
    h_loc, rep = cfg.n_heads // tp, cfg.q_per_kv
    assert (h_loc <= rep and rep % h_loc == 0) or h_loc % rep == 0, \
        "local q-heads must tile kv groups"
    assert cfg.moe is None, "pipeline path covers dense archs"
    return st, tp


# ---------------------------------------------------------------------------
# the pipelined forward
# ---------------------------------------------------------------------------

def build_pipeline_loss(cfg: TransformerConfig, pcfg: PipelineConfig,
                        mesh: Mesh, *, global_batch: int, seq: int):
    """Returns ``loss_fn(params, batch) -> (loss, metrics)`` whose body is a
    shard_map pipeline; differentiate + jit it like any other loss."""
    n_stages, tp = validate(cfg, pcfg, mesh)
    st_ax, tp_ax = pcfg.stage_axis, pcfg.tp_axis
    dp_ax = pcfg.dp_axis if (pcfg.dp_axis in mesh.axis_names) else None
    dp = mesh.shape[dp_ax] if dp_ax else 1
    n_mb = pcfg.microbatches
    assert global_batch % (n_mb * dp) == 0
    mb = global_batch // (n_mb * dp)          # sequences per microbatch
    L_loc = cfg.n_layers // n_stages
    H_loc = cfg.n_heads // tp
    S_loc = seq // tp
    dh = cfg.d_head
    dt = jnp.dtype(cfg.dtype)
    kv_per_q_group = cfg.n_heads // cfg.n_kv_heads

    def stage_block(lp, x_sh, positions, tp_rank):
        """One stage's L_loc layers; x_sh [mb, S_loc, D] seq-sharded."""
        def one_layer(x_sh, i):
            p = jax.tree.map(lambda a: a[i], lp)
            # -- attention (Megatron-SP) --------------------------------
            h_sh = L.rmsnorm(x_sh, p["attn_norm"].astype(jnp.float32),
                             cfg.norm_eps)
            h = lax.all_gather(h_sh, tp_ax, axis=1, tiled=True)  # [mb,S,D]
            q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(dt))
            k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(dt))
            v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(dt))
            if cfg.qkv_bias:
                q = q + p["bq"].astype(dt)
                k = k + p["bk"].astype(dt)
                v = v + p["bv"].astype(dt)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            # GQA under TP: device r's H_loc q-heads span the kv heads
            # [kv0, kv0+KV_loc) (kv-group-major head layout, as in the
            # reference model's [KV, rep] reshape)
            rep = kv_per_q_group
            kv_loc = max(1, H_loc // rep)
            rep_loc = min(rep, H_loc)
            kv0 = (tp_rank * H_loc) // rep
            ks = lax.dynamic_slice_in_dim(k, kv0, kv_loc, axis=2)
            vs = lax.dynamic_slice_in_dim(v, kv0, kv_loc, axis=2)
            B_, S_ = q.shape[0], q.shape[1]
            q5 = q.reshape(B_, S_, kv_loc, rep_loc, dh) * (dh ** -0.5)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", q5, ks,
                           preferred_element_type=jnp.float32)
            causal = positions[None, :] <= positions[:, None]
            s = jnp.where(causal[None, None, None], s, L.NEG_INF)
            a = jax.nn.softmax(s, axis=-1).astype(dt)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", a, vs)
            o = o.reshape(B_, S_, H_loc, dh)
            part = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
            attn_sh = lax.psum_scatter(part, tp_ax, scatter_dimension=1,
                                       tiled=True)
            x_sh = x_sh + attn_sh
            # -- mlp ----------------------------------------------------
            h_sh = L.rmsnorm(x_sh, p["mlp_norm"].astype(jnp.float32),
                             cfg.norm_eps)
            h = lax.all_gather(h_sh, tp_ax, axis=1, tiled=True)
            if cfg.mlp == "swiglu":
                g = jnp.einsum("bsd,df->bsf", h, p["wi_gate"].astype(dt))
                u = jnp.einsum("bsd,df->bsf", h, p["wi_up"].astype(dt))
                hh = jax.nn.silu(g) * u
            else:
                hh = jnp.einsum("bsd,df->bsf", h, p["wi"].astype(dt))
                hh = jnp.square(jax.nn.relu(hh))
            part = jnp.einsum("bsf,fd->bsd", hh, p["wo_mlp"].astype(dt))
            mlp_sh = lax.psum_scatter(part, tp_ax, scatter_dimension=1,
                                      tiled=True)
            return x_sh + mlp_sh

        # (a nested per-layer checkpoint was tried and REFUTED: +24%
        # collective traffic from re-gathering activations in the extra
        # recompute pass, with no peak-memory gain — §Perf pair 1 iter 4)
        for i in range(L_loc):
            x_sh = one_layer(x_sh, i)
        return x_sh

    def body(tokens, labels, *flat_params):
        params = dict(zip(flat_names, flat_params))
        stage = lax.axis_index(st_ax)
        tp_rank = lax.axis_index(tp_ax)
        positions = jnp.arange(seq, dtype=jnp.int32)
        lp = {k.split("/", 1)[1]: v for k, v in params.items()
              if k.startswith("layers/")}
        emb = params["emb"]                      # [V, D_loc]
        head = params["head"] if "head" in params else None
        D_loc = emb.shape[1]

        def embed(tok):                          # [mb, S] -> [mb, S_loc, D]
            e_part = jnp.take(emb, tok, axis=0).astype(dt)  # [mb,S,D_loc]
            e = lax.all_gather(e_part, tp_ax, axis=2, tiled=True)
            return lax.dynamic_slice_in_dim(
                e, tp_rank * S_loc, S_loc, axis=1)

        def loss_of(x_sh, lab):
            # gather seq, final norm, vocab-sharded head + stable sharded CE
            x = lax.all_gather(x_sh, tp_ax, axis=1, tiled=True)
            x = L.rmsnorm(x, params["final_norm"].astype(jnp.float32),
                          cfg.norm_eps)
            if cfg.tie_embeddings:
                # emb is D-sharded → partial matmul over the local D slice
                x_part = lax.dynamic_slice_in_dim(
                    x, tp_rank * D_loc, D_loc, axis=2)
                logits = lax.psum(
                    jnp.einsum("bsd,vd->bsv", x_part, emb.astype(dt),
                               preferred_element_type=jnp.float32), tp_ax)
                lse = jax.nn.logsumexp(logits, axis=-1)
                onehot = lab[..., None] == jnp.arange(
                    logits.shape[-1], dtype=lab.dtype)
                ll = jnp.sum(jnp.where(onehot, logits, 0), axis=-1)
            else:
                logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dt),
                                    preferred_element_type=jnp.float32)
                vlo = tp_rank * logits.shape[-1]
                # max-shift is for stability only; pmax has no VJP, so cut
                # the tape BEFORE it (the lse gradient stays exact)
                mx = lax.pmax(
                    lax.stop_gradient(jnp.max(logits, axis=-1)), tp_ax)
                zsum = lax.psum(
                    jnp.sum(jnp.exp(logits - mx[..., None]), -1), tp_ax)
                lse = jnp.log(zsum) + mx
                onehot = (lab[..., None]
                          == (jnp.arange(logits.shape[-1],
                                         dtype=lab.dtype) + vlo))
                ll = lax.psum(jnp.sum(jnp.where(onehot, logits, 0), -1),
                              tp_ax)
            mask = (lab >= 0).astype(jnp.float32)
            return jnp.sum((lse - ll) * mask), jnp.sum(mask)

        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            x_sh, nll, cnt = carry
            x_in = lax.ppermute(x_sh, st_ax, fwd_perm)
            m0 = jnp.clip(t, 0, n_mb - 1) * mb
            tok = lax.dynamic_slice_in_dim(tokens, m0, mb, axis=0)
            lab = lax.dynamic_slice_in_dim(labels, m0, mb, axis=0)
            x = jnp.where(stage == 0, embed(tok), x_in)
            x = stage_block(lp, x, positions, tp_rank)
            m_last = t - (n_stages - 1)
            m0l = jnp.clip(m_last, 0, n_mb - 1) * mb
            labl = lax.dynamic_slice_in_dim(labels, m0l, mb, axis=0)
            nll_m, cnt_m = loss_of(x, labl)
            # every TP rank of the last stage holds identical (psum'd)
            # values — emit from rank 0 only so the final psum is exact
            emit = ((stage == n_stages - 1) & (tp_rank == 0)
                    & (m_last >= 0) & (m_last < n_mb)).astype(jnp.float32)
            return (x, nll + emit * nll_m, cnt + emit * cnt_m), None

        tick_fn = jax.checkpoint(
            tick, policy=jax.checkpoint_policies.nothing_saveable) \
            if cfg.remat else tick
        # the carry inits must sit on the param (unknown) side of the
        # autodiff partial-eval split: shard_map's transpose rule (jax
        # 0.4.x) zips cotangents against in_names positionally, and
        # known-side residuals that receive linear cotangents (a scan
        # carry init does) shift that pairing and break grad() with a
        # _SpecError; 0 * finite-param keeps the values exactly zero
        zf = 0.0 * emb.ravel()[0].astype(jnp.float32)
        x0 = jnp.zeros((mb, S_loc, cfg.d_model), dt) + zf.astype(dt)
        n_ticks = n_mb + n_stages - 1
        (x_sh, nll, cnt), _ = lax.scan(
            tick_fn, (x0, zf, zf), jnp.arange(n_ticks))
        axes = (st_ax, tp_ax) + ((dp_ax,) if dp_ax else ())
        nll = lax.psum(nll, axes)
        cnt = lax.psum(cnt, axes)
        return nll / jnp.maximum(cnt, 1.0), cnt

    # ---- shard_map wiring ------------------------------------------------
    pspecs = param_pspecs(cfg, pcfg, mesh)
    from repro.models.transformer import model as M
    flat_names = sorted(M.param_shapes(cfg))
    in_param_specs = tuple(pspecs[n] for n in flat_names)
    batch_spec = P(dp_ax) if dp_ax else P()

    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(batch_spec, batch_spec) + in_param_specs,
        out_specs=(P(), P()),
        check_vma=False)

    def loss_fn(params, batch):
        flat = [params[k] for k in flat_names]
        loss, cnt = smapped(batch["tokens"], batch["labels"], *flat)
        return loss, {"loss": loss, "tokens": cnt}

    loss_fn._flat_names = flat_names
    loss_fn._pspecs = {n: pspecs[n] for n in flat_names}
    return loss_fn


def pipeline_param_shardings(cfg: TransformerConfig, pcfg: PipelineConfig,
                             mesh: Mesh) -> Dict[str, NamedSharding]:
    from repro.models.transformer import model as M
    pspecs = param_pspecs(cfg, pcfg, mesh)
    return {k: NamedSharding(mesh, pspecs[k])
            for k in M.param_shapes(cfg)}
