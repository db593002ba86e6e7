"""Decoder-only LM assembly: train forward, prefill, decode, caches.

Covers families: dense / moe / vlm (uniform attention blocks), ssm (RWKV6),
hybrid (Zamba2: Mamba2 backbone + shared tied attention block).

Layer stacking: params are stacked (L, ...) pytrees; the forward pass scans
over *pattern groups* — the repeating layer pattern is unrolled inside the
scan body so per-layer static attributes survive jit (see blocks.py).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN_GLOBAL, RWKV6, ModelConfig
from repro.core.routing import as_routes
from repro.models import attention as attn_mod
from repro.models import blocks as B
from repro.models import layers as L
from repro.models import mamba2 as mamba_mod
from repro.models import rope as rope_mod
from repro.models import rwkv6 as rwkv_mod

PyTree = Any


def _stack_init(fn, key, n):
    return jax.vmap(fn)(jax.random.split(key, n))


def _slice_tree(tree, idx):
    return jax.tree_util.tree_map(lambda a: a[idx], tree)


def _group_tree(tree, g, plen):
    return jax.tree_util.tree_map(
        lambda a: a[: g * plen].reshape((g, plen) + a.shape[1:]), tree)


def _tail_tree(tree, g, plen):
    return jax.tree_util.tree_map(lambda a: a[g * plen:], tree)


def _stack_layers(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def ZERO_AUX():
    return {"aux_loss": jnp.float32(0), "z_loss": jnp.float32(0),
            "drop_frac": jnp.float32(0)}


def remat_wrap(cfg, body):
    """Activation checkpointing for a scanned layer-group body.

    Policies: "full" recomputes everything (min memory);
    "collectives" saves the post-all-reduce activations (tagged
    ``checkpoint_name`` in blocks.py) so the backward recompute never
    re-runs the TP collectives — the §Perf HC-A optimization;
    "dots" saves matmul outputs (max compute savings, max memory).
    """
    if not cfg.remat or cfg.remat_policy == "none":
        return body
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if cfg.remat_policy == "collectives":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "ffn_out"))
    return jax.checkpoint(body)  # "full": recompute everything


def _add_aux(a, b):
    return {k: a[k] + b[k] for k in a}


class LMModel:
    """Functional model: all methods take params explicitly.

    ``routes`` is the Oobleck RoutingPlan (stage -> lowering target); it is
    static — a new routing means a reconfiguration (recompile), exactly as
    in the paper.  The resident (hot-spare) executable instead passes a
    mapping of ResidentRoute handles built inside its trace.
    """

    def __init__(self, cfg: ModelConfig, routes=None):
        assert not cfg.is_encdec, "use encdec.EncDecModel"
        self.cfg = cfg
        self.routes = as_routes(routes)
        self.metas = B.make_metas(cfg)
        self.pattern = cfg.layer_pattern or (ATTN_GLOBAL,)
        self.plen = len(self.pattern)
        if cfg.family == "hybrid":
            self.n_groups = cfg.num_layers // cfg.shared_attn_every
            self.n_tail = cfg.num_layers % cfg.shared_attn_every
        else:
            self.n_groups = cfg.num_layers // self.plen
            self.n_tail = cfg.num_layers % self.plen
        self.compute_dtype = jnp.dtype(cfg.dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)

    # ------------------------------------------------------------- init
    def init(self, key) -> PyTree:
        cfg = self.cfg
        dt = self.param_dtype
        ks = jax.random.split(key, 6)
        params: Dict[str, PyTree] = {
            "embed": L.init_embed(ks[0], cfg.vocab_size, cfg.d_model, dt),
            "final_norm": L.init_norm(cfg.d_model, dt, cfg.use_layernorm),
        }
        kind0 = self.metas[0].kind
        if cfg.family == "hybrid":
            def init_l(k):
                return B.init_mamba_block(k, cfg, dt)
            params["shared"] = B.init_attn_block(ks[2], cfg, dt)
        elif kind0 == RWKV6:
            def init_l(k):
                return B.init_rwkv_block(k, cfg, dt)
        else:
            def init_l(k):
                return B.init_attn_block(k, cfg, dt)
        params["layers"] = _stack_init(init_l, ks[1], cfg.num_layers)
        if not cfg.tie_embeddings:
            params["lm_head"] = L.init_lm_head(ks[3], cfg.d_model,
                                               cfg.vocab_size, dt)
        return params

    # --------------------------------------------------------- backbone
    def _ropes(self, positions, positions3=None):
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        if cfg.mrope_sections:
            if positions3 is None:
                positions3 = jnp.repeat(positions[..., None], 3, axis=-1)
            cs = rope_mod.mrope_tables(positions3, hd, cfg.rope_theta,
                                       cfg.mrope_sections)
            return {"global": cs, "local": cs}
        ropes = {"global": rope_mod.rope_tables(positions, hd, cfg.rope_theta)}
        ropes["local"] = (rope_mod.rope_tables(positions, hd, cfg.rope_theta_local)
                          if cfg.rope_theta_local else ropes["global"])
        return ropes

    def _embed_in(self, params, batch):
        cfg = self.cfg
        if "embeds" in batch:  # stub modality frontend (vlm/audio)
            x = batch["embeds"].astype(self.compute_dtype)
        else:
            x = L.embed(params["embed"], batch["tokens"],
                        scale_by_dim=cfg.embed_scale,
                        compute_dtype=self.compute_dtype)
        return x

    def _logits(self, params, h):
        cfg = self.cfg
        h = L.norm(params["final_norm"], h, eps=cfg.norm_eps,
                   layernorm=cfg.use_layernorm)
        if cfg.tie_embeddings:
            return L.logits_from_embed(params["embed"]["table"], h,
                                       softcap=cfg.final_softcap)
        return L.lm_head(params["lm_head"], h, softcap=cfg.final_softcap)

    def _run_layers(self, params, x, ropes, caches=None, t=None, step=False):
        """Shared layer driver.

        Cache structure (uniform-attention & rwkv families):
          {"grp": tuple_j of stacked (G, ...) caches for pattern position j,
           "tail": tuple_j of single caches for the tail layers}
        An attention cache holds heads-major k/v (B, Hkv, Smax, Dh) and
        ``pos`` (B, Smax) (``attention.init_kv_cache``); stacked, (G, B,
        Hkv, Smax, Dh), and a serving pool adds the slot axis in front.
        Per-position tuples let local/global layers carry different cache
        lengths (ring buffers vs full KV) through one scan.
        """
        cfg = self.cfg
        if cfg.family == "hybrid":
            return self._run_hybrid(params, x, ropes, caches, t, step)
        plen, G, tail = self.plen, self.n_groups, self.n_tail
        metas = self.metas
        kind0 = metas[0].kind
        aux = ZERO_AUX()

        def block_j(j_meta, pj, xx, cj):
            if kind0 == RWKV6:
                xx, cj = B.rwkv_block(pj, xx, cfg, self.routes, state=cj,
                                      step=step)
                return xx, cj, ZERO_AUX()
            return B.attn_block(pj, xx, cfg, j_meta, ropes, self.routes,
                                cache=cj, t=t, step=step)

        if step:
            # decode: unroll the layers.  Scanning would carry the stacked
            # KV caches through the loop and double-buffer them (a full
            # cache copy per layer); unrolled, every update is a single
            # in-place dynamic-update-slice on the donated stacked cache.
            grp = list(caches["grp"]) if G > 0 else []
            for g in range(G):
                for j in range(plen):
                    pj = _slice_tree(params["layers"], g * plen + j)
                    if kind0 == RWKV6:
                        cj = _slice_tree(grp[j], g)
                        x, cj = B.rwkv_block(pj, x, cfg, self.routes,
                                             state=cj, step=True)
                        grp[j] = jax.tree_util.tree_map(
                            lambda full, s: full.at[g].set(s), grp[j], cj)
                    else:
                        x, grp[j], aux_j = B.attn_block(
                            pj, x, cfg, metas[j], ropes, self.routes,
                            cache=grp[j], t=t, step=True, layer=g)
                        aux = _add_aux(aux, aux_j)
            new_tail = []
            for j in range(tail):
                pj = _slice_tree(params["layers"], G * plen + j)
                x, cj, aux_j = block_j(metas[j], pj, x, caches["tail"][j])
                aux = _add_aux(aux, aux_j)
                new_tail.append(cj)
            new_caches = {"grp": tuple(grp) if G > 0 else None,
                          "tail": tuple(new_tail)}
            return x, new_caches, aux

        def group_body(carry, xs):
            xx, aux_c = carry
            p_g, c_g = xs
            new_cs = []
            for j in range(plen):
                pj = _slice_tree(p_g, j)
                cj = c_g[j] if c_g is not None else None
                xx, cj, aux_j = block_j(metas[j], pj, xx, cj)
                aux_c = _add_aux(aux_c, aux_j)
                new_cs.append(cj)
            ys = tuple(new_cs) if c_g is not None else jnp.float32(0)
            return (xx, aux_c), ys

        body = group_body if step else remat_wrap(cfg, group_body)

        new_grp = None
        if G > 0:
            p_groups = _group_tree(params["layers"], G, plen)
            c_grp = caches["grp"] if caches is not None else None
            (x, aux), ys = jax.lax.scan(body, (x, aux), (p_groups, c_grp))
            if caches is not None:
                new_grp = ys
        new_tail = []
        if tail:
            p_tail = _tail_tree(params["layers"], G, plen)
            for j in range(tail):
                pj = _slice_tree(p_tail, j)
                cj = caches["tail"][j] if caches is not None else None
                x, cj, aux_j = block_j(metas[j], pj, x, cj)
                aux = _add_aux(aux, aux_j)
                new_tail.append(cj)
        new_caches = None
        if caches is not None:
            new_caches = {"grp": new_grp, "tail": tuple(new_tail)}
        return x, new_caches, aux

    def _run_hybrid(self, params, x, ropes, caches, t, step):
        """Zamba2: groups of ``shared_attn_every`` mamba layers, each group
        followed by one application of the shared (tied) attention block."""
        cfg = self.cfg
        per = cfg.shared_attn_every
        G, tail = self.n_groups, self.n_tail
        aux = ZERO_AUX()
        meta = B.LayerMeta(kind=ATTN_GLOBAL, window=0, theta=cfg.rope_theta,
                           local=False)
        shared_p = params["shared"]
        m_caches = caches["mamba"] if caches is not None else None
        a_caches = caches["attn"] if caches is not None else None

        if step:
            # unrolled decode (see the uniform path for the rationale)
            for g in range(G):
                for j in range(per):
                    li = g * per + j
                    pj = _slice_tree(params["layers"], li)
                    cj = _slice_tree(m_caches, li)
                    x, cj = B.mamba_block(pj, x, cfg, self.routes,
                                          state=cj, step=True)
                    m_caches = jax.tree_util.tree_map(
                        lambda full, s: full.at[li].set(s), m_caches, cj)
                x, a_caches, aux_j = B.attn_block(
                    shared_p, x, cfg, meta, ropes, self.routes,
                    cache=a_caches, t=t, step=True, layer=g)
                aux = _add_aux(aux, aux_j)
            for j in range(tail):
                li = G * per + j
                pj = _slice_tree(params["layers"], li)
                cj = _slice_tree(m_caches, li)
                x, cj = B.mamba_block(pj, x, cfg, self.routes, state=cj,
                                      step=True)
                m_caches = jax.tree_util.tree_map(
                    lambda full, s: full.at[li].set(s), m_caches, cj)
            return x, {"mamba": m_caches, "attn": a_caches}, aux

        def group_body(carry, xs):
            xx, aux_c = carry
            p_g, mc_g, ac = xs
            new_ms = []
            for j in range(per):
                pj = _slice_tree(p_g, j)
                cj = _slice_tree(mc_g, j) if mc_g is not None else None
                xx, cj = B.mamba_block(pj, xx, cfg, self.routes, state=cj,
                                       step=step)
                new_ms.append(cj)
            xx, ac_new, aux_j = B.attn_block(shared_p, xx, cfg, meta, ropes,
                                             self.routes, cache=ac, t=t,
                                             step=step)
            aux_c = _add_aux(aux_c, aux_j)
            ys = (_stack_layers(new_ms) if mc_g is not None else jnp.float32(0),
                  ac_new if ac is not None else jnp.float32(0))
            return (xx, aux_c), ys

        body = group_body if step else remat_wrap(cfg, group_body)

        p_groups = _group_tree(params["layers"], G, per)
        mc_groups = _group_tree(m_caches, G, per) if caches is not None else None
        (x, aux), (new_mc, new_ac) = jax.lax.scan(
            body, (x, aux), (p_groups, mc_groups, a_caches))

        new_caches = None
        if caches is not None:
            new_m = jax.tree_util.tree_map(
                lambda a: a.reshape((G * per,) + a.shape[2:]), new_mc)
        if tail:
            p_tail = _tail_tree(params["layers"], G, per)
            mc_tail = _tail_tree(m_caches, G, per) if caches is not None else None
            tails = []
            for j in range(tail):
                pj = _slice_tree(p_tail, j)
                cj = _slice_tree(mc_tail, j) if caches is not None else None
                x, cj = B.mamba_block(pj, x, cfg, self.routes, state=cj,
                                      step=step)
                tails.append(cj)
            if caches is not None:
                new_m = jax.tree_util.tree_map(
                    lambda a, b: jnp.concatenate([a, b], 0),
                    new_m, _stack_layers(tails))
        if caches is not None:
            new_caches = {"mamba": new_m, "attn": new_ac}
        return x, new_caches, aux

    # ----------------------------------------------------------- modes
    def forward(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Training forward: returns (loss, metrics)."""
        cfg = self.cfg
        x = self._embed_in(params, batch)
        Bt, S = x.shape[:2]
        positions = rope_mod.positions_default(Bt, S)
        ropes = self._ropes(positions, batch.get("positions3"))
        x, _, aux = self._run_layers(params, x, ropes)
        h = L.norm(params["final_norm"], x, eps=cfg.norm_eps,
                   layernorm=cfg.use_layernorm)
        table = (params["embed"]["table"] if cfg.tie_embeddings
                 else params["lm_head"]["w"])
        loss, denom = L.chunked_xent(
            h, batch["targets"], table, tied=cfg.tie_embeddings,
            softcap=cfg.final_softcap, chunk=cfg.loss_chunk,
            mask=batch.get("loss_mask"))
        metrics = {"xent": loss, "tokens": denom}
        if cfg.moe is not None:
            n = max(1, cfg.num_layers)
            loss = loss + cfg.moe.aux_coef * aux["aux_loss"] / n \
                + cfg.moe.router_z_coef * aux["z_loss"] / n
            metrics.update({k: v / n for k, v in aux.items()})
        metrics["loss"] = loss
        return loss, metrics

    def logits_all(self, params, batch) -> jax.Array:
        """Full (B, S, V) teacher-forced logits (tests / tiny models only)."""
        x = self._embed_in(params, batch)
        Bt, S = x.shape[:2]
        positions = rope_mod.positions_default(Bt, S)
        ropes = self._ropes(positions, batch.get("positions3"))
        x, _, _ = self._run_layers(params, x, ropes)
        return self._logits(params, x)

    def init_cache(self, Bt: int, max_len: int) -> PyTree:
        cfg = self.cfg
        dt = self.compute_dtype
        hd = cfg.resolved_head_dim

        def kv(smax):
            return attn_mod.init_kv_cache(Bt, smax, cfg.num_kv_heads, hd, dt)

        def smax_for(window):
            return min(max_len, window) if window else max_len

        if cfg.family == "hybrid":
            m = _stack_layers([mamba_mod.init_mamba2_state(Bt, cfg, dt)
                               for _ in range(cfg.num_layers)])
            a = _stack_layers([kv(smax_for(0))
                               for _ in range(self.n_groups)])
            return {"mamba": m, "attn": a}
        G, tail, plen = self.n_groups, self.n_tail, self.plen
        if self.metas[0].kind == RWKV6:
            def mk(j):
                return rwkv_mod.init_rwkv6_state(Bt, cfg, dt)
        else:
            def mk(j):
                return kv(smax_for(self.metas[j].window))
        grp = (tuple(_stack_layers([mk(j) for _ in range(G)])
                     for j in range(plen)) if G > 0 else None)
        return {"grp": grp, "tail": tuple(mk(j) for j in range(tail))}

    def prefill(self, params, batch) -> Tuple[jax.Array, PyTree]:
        """Prefill: runs the full prompt, returns (last-token logits, cache).

        The cache must be passed in ``batch['cache']`` (pre-allocated to the
        serving max length) so shardings are explicit at the jit boundary.
        """
        x = self._embed_in(params, batch)
        Bt, S = x.shape[:2]
        positions = rope_mod.positions_default(Bt, S)
        ropes = self._ropes(positions, batch.get("positions3"))
        x, caches, _ = self._run_layers(params, x, ropes,
                                        caches=batch["cache"])
        logits = self._logits(params, x[:, -1:])
        return logits, caches

    def decode_step(self, params, cache, tokens, t) -> Tuple[jax.Array, PyTree]:
        """One token: tokens (B, 1), t scalar int32 absolute position."""
        x = self._embed_in(params, {"tokens": tokens})
        ropes = None  # decode blocks compute their own tables from t
        x, caches, _ = self._run_layers(params, x, ropes, caches=cache,
                                        t=t, step=True)
        logits = self._logits(params, x)
        return logits, caches
