"""Decoder-only LM covering the dense / vlm / moe / hybrid / ssm families.

Layers are stacked on a leading axis and executed with ``lax.scan`` (+ full
per-layer remat), so the HLO is O(1) in depth — this keeps the 512-device
dry-run compiles fast and is the standard production layout (MaxText-style).

Three entry points per model:
  loss(params, batch)                     — train_4k
  prefill(params, tokens)                 — prefill_32k (logits + cache/state)
  decode_step(params, cache, token, pos)  — decode_32k / long_500k
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.sharding.ctx import ShardCtx
from repro.models import layers as L
from repro.models import ssm as S


class LM:
    def __init__(self, cfg: ModelConfig, ctx: Optional[ShardCtx] = None, *,
                 q_chunk: int = 256, loss_chunk: int = 1024, remat: bool = True,
                 long_decode_threshold: int = 65536, kv_quant: bool = False):
        assert cfg.family in ("dense", "vlm", "moe", "hybrid", "ssm")
        self.cfg = cfg
        self.ctx = ctx or ShardCtx.null()
        self.q_chunk = q_chunk
        self.loss_chunk = loss_chunk
        self.remat = remat
        self.long_decode_threshold = long_decode_threshold
        # int8 KV cache with per-(position, kv-head) scales: halves (vs
        # bf16) serving cache memory — the lever that fits MHA-32 × 32k
        # decode on a 16 GiB chip (EXPERIMENTS.md §Known-issues)
        self.kv_quant = kv_quant
        self.dtype = jnp.dtype(cfg.param_dtype)
        self._layer_axes = L.axes_from_spec(self.layer_spec())

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------
    def layer_spec(self) -> Dict[str, Tuple[Tuple[int, ...], Tuple]]:
        cfg = self.cfg
        d = cfg.d_model
        spec: Dict[str, Any] = {}
        if cfg.family == "ssm":
            spec["ln1"] = ((d,), (None,))
            spec["ln2"] = ((d,), (None,))
            spec.update(S.rwkv_param_spec(cfg))
            return spec
        spec["ln1"] = ((d,), (None,))
        spec.update(L.attn_param_spec(cfg))
        if not cfg.parallel_block:
            spec["ln2"] = ((d,), (None,))
        if cfg.family == "moe":
            spec.update(L.moe_param_spec(cfg))
        else:
            spec.update(L.mlp_param_spec(cfg))
        if cfg.family == "hybrid":
            spec.update({f"mamba_{k}": v for k, v in S.mamba_param_spec(cfg).items()})
            spec["attn_out_ln"] = ((d,), (None,))
            spec["mamba_out_ln"] = ((d,), (None,))
        return spec

    def top_spec(self):
        cfg = self.cfg
        vp, d = cfg.padded_vocab(), cfg.d_model
        spec = {
            "embed": ((vp, d), ("vocab", "d_model")),
            "final_ln": ((d,), (None,)),
        }
        if not cfg.tie_embeddings:
            spec["lm_head"] = ((d, vp), ("d_model", "vocab"))
        return spec

    def init_params(self, key: jax.Array):
        cfg = self.cfg
        lkeys = jax.random.split(jax.random.fold_in(key, 1), cfg.n_layers)
        lspec = self.layer_spec()
        layer_params = jax.vmap(
            lambda k: L.init_from_spec(k, lspec, self.dtype))(lkeys)
        top = L.init_from_spec(jax.random.fold_in(key, 0), self.top_spec(),
                               self.dtype)
        return {"layers": layer_params, **top}

    def param_axes(self):
        lax_ = {k: ("layer",) + v for k, v in
                L.axes_from_spec(self.layer_spec()).items()}
        return {"layers": lax_, **L.axes_from_spec(self.top_spec())}

    def abstract_params(self):
        return jax.eval_shape(lambda: self.init_params(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------------
    # blocks
    # ------------------------------------------------------------------
    def _attn(self, x, p, positions, mode, cache=None, pos=None):
        """mode: 'par' (train/prefill) or 'dec'.  Returns (out, (k,v))."""
        cfg, ctx = self.cfg, self.ctx
        q, k, v = L._project_qkv(x, p, cfg, ctx, positions)
        if mode in ("par", "par_cache"):
            if ctx.attn_impl == "cp" and ctx.enabled:
                out = L.attention_context_parallel(
                    q, k, v, ctx=ctx, q_chunk=self.q_chunk,
                    softcap=cfg.logit_softcap)
            else:
                out = L.attention_chunked(q, k, v, causal=True, ctx=ctx,
                                          q_chunk=self.q_chunk,
                                          softcap=cfg.logit_softcap)
            new_kv = (k, v)
        else:
            # ``cache`` is this layer's stored K/V, only read here: the
            # token attends to its ``pos`` cached positions (``pos`` a
            # scalar, or a [B] vector of per-slot positions for ragged
            # decode) and to its own k/v, and returns that row in the
            # cache's layout, which ``decode_step`` writes for every layer
            # at once
            if self.kv_quant:
                k_cache, v_cache, ks_cache, vs_cache = cache
                kq, ks = L.kv_quantize(k)
                vq, vs = L.kv_quantize(v)
                new_kv = tuple(L.to_cache_layout(a, 1)
                               for a in (kq, vq, ks, vs))
                k_new = L.kv_dequantize(new_kv[0], new_kv[2])
                v_new = L.kv_dequantize(new_kv[1], new_kv[3])
                kw = {"k_scale": ks_cache, "v_scale": vs_cache}
            else:
                k_cache, v_cache = cache
                new_kv = k_new, v_new = (L.to_cache_layout(k, 1),
                                         L.to_cache_layout(v, 1))
                kw = {}
            kw.update(k_new=k_new, v_new=v_new)
            length = L.decode_lengths(pos, x.shape[0])
            if ctx.enabled and ctx.decode_kv == "dp_seq":
                out = L.flash_decode_sharded(q, k_cache, v_cache, ctx, length,
                                             seq_axes=ctx.dp, batch_axes=(),
                                             **kw)
            elif ctx.enabled and ctx.decode_kv == "tp_seq":
                out = L.flash_decode_sharded(q, k_cache, v_cache, ctx, length,
                                             seq_axes=(ctx.tp,),
                                             batch_axes=ctx.dp, **kw)
            else:
                out = L.attention_decode(q, k_cache, v_cache, length,
                                         cfg.logit_softcap, **kw)
        out = jnp.einsum("bsq,qd->bsd",
                         out.reshape(x.shape[0], x.shape[1], -1), p["wo"])
        return out, new_kv

    def _block(self, x, p, positions, mode, cache=None, pos=None,
               want_aux=False, layer=None):
        """One transformer block.  Returns (x, new_cache, aux).  With
        ``layer``, ``p``'s routed-expert weights are the whole stack
        (``_split_experts``)."""
        cfg, ctx = self.cfg, self.ctx
        aux = jnp.zeros((), jnp.float32)
        if cfg.family == "ssm":
            h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
            tm_out, (shift_tm, wkv) = S.rwkv_time_mix(
                h, p, cfg, ctx,
                shift_state=cache["shift_tm"] if cache else jnp.zeros(
                    (x.shape[0], cfg.d_model), x.dtype),
                wkv_state=cache["wkv"] if (cache and mode == "dec") else None)
            x = x + tm_out
            h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            cm_out, shift_cm = S.rwkv_channel_mix(
                h, p, cfg, ctx,
                shift_state=cache["shift_cm"] if cache else jnp.zeros(
                    (x.shape[0], cfg.d_model), x.dtype))
            x = x + cm_out
            if mode == "par":          # train: drop state, let XLA DCE it
                new_cache = {}
            else:
                new_cache = {"wkv": wkv.astype(jnp.float32),
                             "shift_tm": shift_tm, "shift_cm": shift_cm}
            return x, new_cache, aux

        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        if cache is not None and self.kv_quant:
            attn_cache = (cache["k"], cache["v"], cache["k_scale"],
                          cache["v_scale"])
        elif cache is not None:
            attn_cache = (cache["k"], cache["v"])
        else:
            attn_cache = None
        attn_out, new_kv = self._attn(h, p, positions, mode,
                                      cache=attn_cache, pos=pos)
        new_cache: Dict[str, Any] = {}
        if cache is not None or mode == "par_cache":
            new_cache.update({"k": new_kv[0], "v": new_kv[1]})
            if self.kv_quant and len(new_kv) == 4:
                new_cache.update({"k_scale": new_kv[2], "v_scale": new_kv[3]})

        if cfg.family == "hybrid":
            mp = {k[len("mamba_"):]: v for k, v in p.items()
                  if k.startswith("mamba_")}
            m_state = ({"conv": cache["conv"], "ssm": cache["ssm"]}
                       if (cache and mode == "dec") else None)
            mamba_out, m_new = S.mamba_block(h, mp, cfg, ctx, state=m_state)
            # mean of per-branch normalized outputs (hymba parallel heads)
            attn_out = L.rms_norm(attn_out, p["attn_out_ln"], cfg.norm_eps)
            mamba_out = L.rms_norm(mamba_out, p["mamba_out_ln"], cfg.norm_eps)
            attn_out = 0.5 * (attn_out + mamba_out)
            if cache is not None or mode == "par_cache":
                new_cache.update({"conv": m_new["conv"],
                                  "ssm": m_new["ssm"].astype(jnp.float32)})

        if cfg.parallel_block:
            x = x + attn_out + L.mlp(h, p, cfg, ctx)
            return x, new_cache, aux

        x = x + attn_out
        x = self.ctx.constrain(x, "batch", "seq" if mode == "par" else None, None)
        h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            if want_aux:
                aux = L.moe_aux_loss(h2, p, cfg)
            x = x + L.moe_block(h2, p, cfg, ctx, layer=layer)
        else:
            x = x + L.mlp(h2, p, cfg, ctx)
        x = self.ctx.constrain(x, "batch", "seq" if mode == "par" else None, None)
        return x, new_cache, aux

    # ------------------------------------------------------------------
    # forward passes
    # ------------------------------------------------------------------
    def _embed(self, params, tokens):
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.dtype)
        return self.ctx.constrain(x, "batch", None, None)

    def _split_experts(self, params, served: bool):
        """(the weights the layer scan slices, the routed experts' weights
        stacked over layers).  The second is filled for a served pass (not
        differentiated) on one device, where the expert kernel reads each
        layer's block where it lies: sliced by the scan, each layer's held
        experts would be copied out whole first, all of them read whether
        any token picked them or not."""
        layers = params["layers"]
        if not served or self.cfg.family != "moe" or self.ctx.enabled:
            return layers, {}
        whole = {k: layers[k] for k in L.EXPERT_WEIGHTS}
        return {k: v for k, v in layers.items() if k not in whole}, whole

    def forward(self, params, tokens, *, want_aux=False, collect_cache=False):
        """Parallel forward over [B, S].  Returns (hidden, cache, aux)."""
        x = self._embed(params, tokens)
        positions = jnp.arange(tokens.shape[1])[None, :]
        mode = "par_cache" if collect_cache else "par"
        layers, whole = self._split_experts(params, collect_cache)

        def body(x, xs):
            lp, layer = xs
            # explicit FSDP gather: all-gather this layer's weights over the
            # data axes (reverse = gradient reduce-scatter)
            lp = self.ctx.gather_params(lp, self._layer_axes)
            x, cache_l, aux = self._block(
                x, dict(lp, **whole), positions, mode, want_aux=want_aux,
                layer=layer if whole else None)
            return x, (cache_l, aux)

        if self.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, (cache, auxs) = lax.scan(
            body, x, (layers, jnp.arange(self.cfg.n_layers)))
        x = L.rms_norm(x, params["final_ln"], self.cfg.norm_eps)
        return x, cache, jnp.sum(auxs)

    def logits_fn(self, params, hidden, *, gather: bool = False):
        cfg = self.cfg
        if cfg.tie_embeddings:
            head = params["embed"]
            if gather:
                head = self.ctx.gather_fsdp(head, ("vocab", "d_model"))
            head = head.T
        else:
            head = params["lm_head"]
            if gather:
                head = self.ctx.gather_fsdp(head, ("d_model", "vocab"))
        logits = jnp.einsum("bsd,dv->bsv", hidden, head).astype(jnp.float32)
        vp = cfg.padded_vocab()
        if vp != cfg.vocab_size:
            mask = jnp.arange(vp) < cfg.vocab_size
            logits = jnp.where(mask[None, None, :], logits, L.NEG_INF)
        return self.ctx.constrain(logits, "batch", None, "vocab")

    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """batch: {'tokens': [B,S], 'targets': [B,S]} (-1 = padding)."""
        tokens, targets = batch["tokens"], batch["targets"]
        hidden, _, aux = self.forward(params, tokens, want_aux=True)
        B, Sq, _ = hidden.shape
        c = min(self.loss_chunk, Sq)
        assert Sq % c == 0
        hc = hidden.reshape(B, Sq // c, c, -1).transpose(1, 0, 2, 3)
        tc = targets.reshape(B, Sq // c, c).transpose(1, 0, 2)

        def chunk(carry, xs):
            h, t = xs
            logits = self.logits_fn(params, h, gather=True)
            logp = jax.nn.log_softmax(logits, axis=-1)
            valid = t >= 0
            tsafe = jnp.where(valid, t, 0)
            nll = -jnp.take_along_axis(logp, tsafe[..., None], axis=-1)[..., 0]
            total, count = carry
            return (total + jnp.sum(nll * valid), count + jnp.sum(valid)), None

        (total, count), _ = lax.scan(chunk, (jnp.zeros((), jnp.float32),
                                             jnp.zeros((), jnp.float32)),
                                     (hc, tc))
        loss = total / jnp.maximum(count, 1.0)
        if self.cfg.family == "moe":
            loss = loss + 0.01 * aux / self.cfg.n_layers
        return loss, {"nll": total / jnp.maximum(count, 1.0), "aux": aux}

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def cache_shapes(self, batch: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        Lc, hd = cfg.n_layers, cfg.resolved_head_dim
        shapes: Dict[str, Any] = {}
        if cfg.family != "ssm":
            # the decode dots' layout, sequence minor (layers.py)
            kv = (Lc, batch, cfg.n_kv_heads, hd, max_len)
            kv_dtype = jnp.int8 if self.kv_quant else self.dtype
            shapes["k"] = jax.ShapeDtypeStruct(kv, kv_dtype)
            shapes["v"] = jax.ShapeDtypeStruct(kv, kv_dtype)
            if self.kv_quant:
                sc = (Lc, batch, cfg.n_kv_heads, 1, max_len)
                shapes["k_scale"] = jax.ShapeDtypeStruct(sc, jnp.bfloat16)
                shapes["v_scale"] = jax.ShapeDtypeStruct(sc, jnp.bfloat16)
        if cfg.family == "hybrid":
            ms = S.mamba_state_shape(cfg, batch)
            shapes["conv"] = jax.ShapeDtypeStruct((Lc,) + ms["conv"], self.dtype)
            shapes["ssm"] = jax.ShapeDtypeStruct((Lc,) + ms["ssm"], jnp.float32)
        if cfg.family == "ssm":
            rs = S.rwkv_state_shape(cfg, batch)
            shapes["wkv"] = jax.ShapeDtypeStruct((Lc,) + rs["wkv"], jnp.float32)
            shapes["shift_tm"] = jax.ShapeDtypeStruct((Lc,) + rs["shift_tm"], self.dtype)
            shapes["shift_cm"] = jax.ShapeDtypeStruct((Lc,) + rs["shift_cm"], self.dtype)
        return shapes

    def cache_axes(self) -> Dict[str, Tuple]:
        cfg = self.cfg
        ax: Dict[str, Tuple] = {}
        if cfg.family != "ssm":
            # batch over dp; kv heads over tp when divisible.  'kv_seq' is
            # replicated by default; long_500k maps it to the dp axes and
            # flash_decode_sharded combines the shards (DESIGN.md §5).
            ax["k"] = ("layer", "batch", "kv_heads", None, "kv_seq")
            ax["v"] = ("layer", "batch", "kv_heads", None, "kv_seq")
            if self.kv_quant:
                ax["k_scale"] = ("layer", "batch", "kv_heads", None, "kv_seq")
                ax["v_scale"] = ("layer", "batch", "kv_heads", None, "kv_seq")
        if cfg.family == "hybrid":
            ax["conv"] = ("layer", "batch", None, "ffn")
            ax["ssm"] = ("layer", "batch", "heads", None, None)
        if cfg.family == "ssm":
            ax["wkv"] = ("layer", "batch", "heads", None, None)
            ax["shift_tm"] = ("layer", "batch", None)
            ax["shift_cm"] = ("layer", "batch", None)
        return ax

    def init_cache(self, batch: int, max_len: int):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_shapes(batch, max_len))

    def prefill(self, params, tokens, max_len: Optional[int] = None,
                lengths: Optional[jax.Array] = None):
        """Returns (last_token_logits, cache ready at pos=S).

        ``lengths`` [B] (optional) marks each row's true prompt length in a
        right-padded packed batch: the returned logits are taken at column
        ``lengths-1`` per row instead of the last column.  Under causal
        attention the pad tail never influences earlier positions, so a
        packed bucketed prefill is exactly equivalent to per-request
        prefills (pad K/V beyond ``lengths`` is masked out at decode by the
        per-slot length).
        """
        cfg = self.cfg
        B, Sq = tokens.shape
        max_len = max_len or Sq
        hidden, cache, _ = self.forward(params, tokens, collect_cache=True)
        if lengths is None:
            h_last = hidden[:, -1:, :]
        else:
            idx = jnp.clip(lengths.astype(jnp.int32) - 1, 0, Sq - 1)
            h_last = jnp.take_along_axis(hidden, idx[:, None, None], axis=1)
        # under cp the head rests sharded over all axes: a full gather would
        # materialize V×d (4.2 GB for command-r); psum of [B,1,V] is cheaper
        logits = self.logits_fn(params, h_last,
                                gather=self.ctx.attn_impl != "cp")
        full = self.init_cache(B, max_len)
        if cfg.family != "ssm":
            new = {"k": cache["k"], "v": cache["v"]}     # [L, B, S, KV, hd]
            if self.kv_quant:
                new["k"], new["k_scale"] = L.kv_quantize(new["k"])
                new["v"], new["v_scale"] = L.kv_quantize(new["v"])
            for key, x in new.items():
                full[key] = lax.dynamic_update_slice(
                    full[key], L.to_cache_layout(x, 2).astype(full[key].dtype),
                    (0, 0, 0, 0, 0))
        for key in ("conv", "ssm", "wkv", "shift_tm", "shift_cm"):
            if key in full:
                full[key] = cache[key].astype(full[key].dtype)
        return logits, full

    def decode_step(self, params, cache, token, pos):
        """token [B,1] int32; pos scalar int32 (current cache length) or a
        [B] int32 vector of per-slot cache lengths (ragged decode: each
        continuous-batching slot advances independently).
        Returns (logits [B,1,V], new_cache).

        The stored K/V is no input or output of the layer scan: each layer
        reads its slice of the stacked cache in place, and the scan emits
        only the new rows, [L, B, KV, hd, 1], which one select per tensor
        writes after it.  Recurrent state is small and goes through the
        scan per layer."""
        x = self._embed(params, token)
        pos = jnp.asarray(pos, jnp.int32)
        if pos.ndim == 0:
            positions = jnp.full((1, 1), pos, jnp.int32)
        else:
            positions = pos[:, None]                     # [B, 1] per-slot
        kv = {k: c for k, c in cache.items()
              if k in ("k", "v", "k_scale", "v_scale")}
        state = {k: c for k, c in cache.items() if k not in kv}
        layers, whole = self._split_experts(params, True)

        def body(x, xs):
            lp, state_l, layer = xs
            cache_l = dict(state_l, **{k: c[layer] for k, c in kv.items()})
            x, new_l, _ = self._block(x, dict(lp, **whole), positions, "dec",
                                      cache=cache_l, pos=pos,
                                      layer=layer if whole else None)
            return x, new_l

        x, new = lax.scan(body, x, (layers, state,
                                    jnp.arange(self.cfg.n_layers)))
        x = L.rms_norm(x, params["final_ln"], self.cfg.norm_eps)
        logits = self.logits_fn(params, x)
        new.update({k: L.cache_update(c, new[k], pos) for k, c in kv.items()})
        return logits, new
