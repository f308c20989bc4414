"""Public model API: build_model(cfg) -> Model with init / forward / loss /
init_paged_cache / prefill / decode_step, uniform across all families
(dense, moe, hybrid, ssm, vlm, audio)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.lora import as_adapter_set
from repro.core.quant import ELIGIBLE
from repro.kernels import dispatch
from repro.models.layers import norm_params, apply_norm
from repro.models.transformer import (apply_stack, banked_scan_layout,
                                      batched_scan_layout, decode_stack,
                                      init_stack, init_paged_stack_cache,
                                      prefill_stack)

PATCH_EMBED_DIM = 1152   # SigLIP stub output width (arXiv:2407.07726)


def pad_vocab(v: int, multiple: int = 256) -> int:
    return -(-v // multiple) * multiple


class Model:
    def __init__(self, cfg):
        self.cfg = cfg
        self.vocab_padded = pad_vocab(cfg.vocab_size)

    # ------------------------------------------------------------- params
    def init(self, key):
        cfg = self.cfg
        pdt = jnp.dtype(cfg.param_dtype)
        ke, ks, kh, kenc, kp = jax.random.split(key, 5)
        params = {
            "embed": (jax.random.normal(ke, (self.vocab_padded, cfg.d_model))
                      * cfg.d_model ** -0.5).astype(pdt),
            "stack": init_stack(cfg, ks),
        }
        params.update(norm_params(cfg, cfg.d_model, "final"))
        if not cfg.tie_embeddings:
            params["lm_head"] = (jax.random.normal(
                kh, (cfg.d_model, self.vocab_padded)) *
                cfg.d_model ** -0.5).astype(pdt)
        if cfg.family == "audio":
            enc = {"stack": init_stack(cfg, kenc,
                                       num_layers=cfg.encoder_layers,
                                       pattern=("attn",))}
            enc.update(norm_params(cfg, cfg.d_model, "encfinal"))
            params["encoder"] = enc
        if cfg.family == "vlm":
            params["patch_proj"] = (jax.random.normal(
                kp, (PATCH_EMBED_DIM, cfg.d_model)) *
                PATCH_EMBED_DIM ** -0.5).astype(pdt)
        return params

    def dot_only(self, path) -> bool:
        """Whether the base leaf at ``path`` (its dict keys from the root)
        is read only as a matrix-product operand: the dense projections that
        route through ``linear`` (``core/quant.ELIGIBLE``) and an untied
        ``lm_head``.  The embedding is gathered (and is the head when tied),
        so it is not; nor are norms, gates, routers or biases."""
        if path == ("lm_head",):
            return not self.cfg.tie_embeddings
        return len(path) >= 2 and path[-1] in ELIGIBLE.get(path[-2], ())

    # ------------------------------------------------------------- forward
    def _embed(self, params, batch):
        cfg = self.cfg
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        x = x.astype(jnp.dtype(cfg.dtype))
        if cfg.family == "vlm":
            patches = (batch["patches"] @ params["patch_proj"]).astype(x.dtype)
            x = jnp.concatenate([patches, x], axis=1)
        return x

    def _encode(self, params, batch):
        cfg = self.cfg
        enc = params["encoder"]
        h, _ = apply_stack(cfg, enc["stack"],
                           batch["frames"].astype(jnp.dtype(cfg.dtype)),
                           causal=False, pattern=("attn",))
        return apply_norm(cfg, h, enc, "encfinal")

    @staticmethod
    def _stack_adapters(adapters):
        """Resolve an AdapterSet to the prepared "stack" subtree the block
        machinery consumes: rank mask applied, gamma folded into B (the one
        place scaling meets the model), banked per-request trees reordered
        for the layer scans."""
        if adapters is None:
            return None
        prepared = adapters.prepared()
        tree = (prepared.lora or {}).get("stack")
        if adapters.batched and tree:
            tree = (banked_scan_layout(tree, adapters.ids)
                    if adapters.ids is not None else
                    batched_scan_layout(tree))
        return tree

    def forward(self, params, batch, adapters=None, *, saved=()):
        """Full-sequence forward.  Returns (logits, aux_loss).

        ``adapters`` is an :class:`repro.core.lora.AdapterSet` (or None for
        the base model).  ``saved``: the tensors of
        ``transformer.SAVEABLE`` the layer scan keeps for a backward."""
        adapters = as_adapter_set(adapters)
        cfg = self.cfg
        with dispatch.scope(cfg.use_pallas):
            x = self._embed(params, batch)
            b, s, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
            enc_out = (self._encode(params, batch)
                       if cfg.family == "audio" else None)
            x, aux = apply_stack(cfg, params["stack"], x,
                                 adapters=self._stack_adapters(adapters),
                                 positions=positions, enc_out=enc_out,
                                 causal=cfg.family != "encoder", saved=saved)
            x = apply_norm(cfg, x, params, "final")
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            logits = x @ head.astype(x.dtype)
        return logits, aux

    def loss(self, params, batch, adapters=None, *, saved=()):
        """Next-token CE over the text segment (+ MoE aux).  Encoder-only
        models use MLM-style loss (mask every 5th token).

        ``adapters`` is an AdapterSet (or None for the base model);
        ``saved`` as in :meth:`forward`."""
        adapters = as_adapter_set(adapters)
        cfg = self.cfg
        tokens = batch["tokens"]
        if cfg.family == "encoder":
            s = tokens.shape[1]
            mask_id = self.vocab_padded - 1
            masked_pos = (jnp.arange(s) % 5) == 2
            inp = jnp.where(masked_pos[None, :], mask_id, tokens)
            logits, aux = self.forward(params, {**batch, "tokens": inp},
                                       adapters=adapters, saved=saved)
            lf = logits.astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(lf, axis=-1)
            ll = jnp.take_along_axis(lf, tokens[..., None], axis=-1)[..., 0]
            per_tok = (lse - ll) * masked_pos[None, :]
            ce = per_tok.sum() / (masked_pos.sum() * tokens.shape[0])
            return ce + aux, {"ce": ce, "aux": aux}
        from repro.sharding import opts
        if opts.enabled("chunked_ce"):
            return self._loss_chunked(params, batch, adapters, saved)
        logits, aux = self.forward(params, batch, adapters=adapters,
                                   saved=saved)
        s_text = tokens.shape[1]
        logits = logits[:, -s_text:][:, :-1]
        labels = tokens[:, 1:]
        lf = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
        ce = (lse - ll).mean()
        return ce + aux, {"ce": ce, "aux": aux}

    def _loss_chunked(self, params, batch, adapters, saved,
                      chunk: int = 512):
        """CE computed in sequence chunks: the full (b, s, V) logits tensor
        never materializes — the head matmul + logsumexp + label gather run
        per chunk inside a scan (beyond-paper memory-term optimization)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        with dispatch.scope(cfg.use_pallas):
            x = self._embed(params, batch)
            b, s, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
            enc_out = (self._encode(params, batch)
                       if cfg.family == "audio" else None)
            x, aux = apply_stack(cfg, params["stack"], x,
                                 adapters=self._stack_adapters(adapters),
                                 positions=positions, enc_out=enc_out,
                                 causal=cfg.family != "encoder", saved=saved)
            x = apply_norm(cfg, x, params, "final")
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        s_text = tokens.shape[1]
        x = x[:, -s_text:][:, :-1]                    # predict positions
        labels = tokens[:, 1:]
        sl = x.shape[1]
        c = min(chunk, sl)
        pad = (-sl) % c
        xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        lp = jnp.pad(labels, ((0, 0), (0, pad)))
        valid = jnp.pad(jnp.ones((b, sl), bool), ((0, 0), (0, pad)))
        nc = xp.shape[1] // c
        xc = xp.reshape(b, nc, c, -1).swapaxes(0, 1)
        lc = lp.reshape(b, nc, c).swapaxes(0, 1)
        vc = valid.reshape(b, nc, c).swapaxes(0, 1)

        def chunk_step(tot, xs):
            xb, lb, vb = xs
            logits = (xb @ head.astype(xb.dtype)).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, lb[..., None], -1)[..., 0]
            return tot + jnp.sum((lse - ll) * vb), None

        tot, _ = jax.lax.scan(jax.checkpoint(chunk_step),
                              jnp.zeros((), jnp.float32), (xc, lc, vc))
        ce = tot / (b * sl)
        return ce + aux, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------- serving
    def init_paged_cache(self, num_blocks: int, block_size: int, batch: int,
                         dtype=None):
        """Serving cache: per-layer KV pools of ``num_blocks`` x
        ``block_size`` slots shared by every request through per-request
        block tables, plus per-slot recurrent/cross state for ``batch``
        engine slots.  The scheduler reserves block 0 as the null block
        idle slots write into (see launch/serve.py's allocator)."""
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.dtype)
        cross = cfg.encoder_frames if cfg.family == "audio" else 0
        return init_paged_stack_cache(cfg, num_blocks, block_size, batch,
                                      dtype, cross_len=cross)

    def prefill(self, params, cache, tokens, adapters=None, *, table,
                enc_out=None, last_only=False):
        """Whole-prompt forward that fills the requests' cache in one
        batched pass: tokens (b, p) int32 -> (logits (b, p, V), new_cache).
        ``last_only=True`` projects only the final position through the
        lm head (logits (b, 1, V)) — generation consumes just that row, and
        at real vocab scale the head GEMM over every prompt position is the
        prefill's dominant wasted work.

        ``cache`` is an :meth:`init_paged_cache` pool whose per-slot state
        is fresh for these ``b`` requests, and ``table`` (b,
        blocks_per_req) int32 names each request's pool blocks.  The token
        at position t lands in virtual-ring slot ``t % vlen`` (vlen =
        blocks_per_req * block_size): pool block ``table[i, (t % vlen) //
        block_size]``, offset ``(t % vlen) % block_size``.  The cache comes
        back as ``p`` sequential :meth:`decode_step` calls would have left
        it (pool blocks, recurrence states, conv tails), so generation is
        one prefill + a decode loop instead of feeding the prompt through
        single-token steps.  ``adapters`` as in decode_step — None, an
        AdapterSet, or a banked per-request set from
        ``AdapterBank.gather``/``requests``.  Encoder-decoder (audio)
        models pass the encoder output as ``enc_out`` so the per-layer
        cross K/V land in the cache."""
        adapters = as_adapter_set(adapters)
        cfg = self.cfg
        with dispatch.scope(cfg.use_pallas):
            x = jnp.take(params["embed"], tokens,
                         axis=0).astype(jnp.dtype(cfg.dtype))
            b, s, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
            x, _, new_cache = prefill_stack(
                cfg, params["stack"], cache, x, positions,
                adapters=self._stack_adapters(adapters), enc_out=enc_out,
                table=table)
            x = apply_norm(cfg, x, params, "final")
            if last_only:
                x = x[:, -1:]
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            logits = x @ head.astype(x.dtype)
        return logits, new_cache

    def decode_step(self, params, cache, token, pos, adapters=None, *,
                    table):
        """One token: token (b,1) int32, pos (b,) absolute position.
        Returns (logits (b,1,V), new_cache).

        ``cache`` is an :meth:`init_paged_cache` pool and ``table`` (b,
        blocks_per_req) int32 each request's blocks in it, as in
        :meth:`prefill`; the step writes position ``pos`` into its
        virtual-ring slot and attends over the ring.  ``adapters`` may be a
        single AdapterSet or a ``batched`` one from ``AdapterBank.gather``
        (one adapter per batch row — multi-tenant serving)."""
        adapters = as_adapter_set(adapters)
        cfg = self.cfg
        with dispatch.scope(cfg.use_pallas):
            x = jnp.take(params["embed"], token,
                         axis=0).astype(jnp.dtype(cfg.dtype))
            x, new_cache = decode_stack(cfg, params["stack"], cache, x, pos,
                                        adapters=self._stack_adapters(
                                            adapters),
                                        table=table)
            x = apply_norm(cfg, x, params, "final")
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            logits = x @ head.astype(x.dtype)
        return logits, new_cache

    # ------------------------------------------------------------- specs
    def input_specs(self, shape, *, n_clients: int = 0, dtype=None):
        """ShapeDtypeStruct stand-ins for every model input of an InputShape.

        For train shapes with ``n_clients``>0 the batch gets a leading client
        dim (global_batch = n_clients * per_client).  Modality frontends are
        stubs: precomputed frame/patch embeddings of the right shape."""
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct

        def batch_spec(b, s):
            d = {"tokens": sds((b, s), i32)}
            if cfg.family == "vlm":
                d["tokens"] = sds((b, s - cfg.num_patches), i32)
                d["patches"] = sds((b, cfg.num_patches, PATCH_EMBED_DIM), dt)
            if cfg.family == "audio":
                d["frames"] = sds((b, cfg.encoder_frames, cfg.d_model), dt)
            return d

        if shape.kind == "train":
            b, s = shape.global_batch, shape.seq_len
            if n_clients:
                per = b // n_clients
                spec = batch_spec(per, s)
                return {k: sds((n_clients,) + v.shape, v.dtype)
                        for k, v in spec.items()}
            return batch_spec(b, s)
        if shape.kind == "prefill":
            return batch_spec(shape.global_batch, shape.seq_len)
        # decode: one token against a pool holding each request's virtual
        # ring of seq_len positions (the window, where one caps it) in
        # blocks of 8; every slot is live, so the plan's table is
        # arange(b * mb).reshape(b, mb) and needs no null block
        b = shape.global_batch
        ring = min(shape.seq_len, cfg.attn_window or shape.seq_len)
        mb = -(-ring // 8)
        cache = jax.eval_shape(
            lambda: self.init_paged_cache(b * mb, 8, b, dtype=dt))
        return {"token": sds((b, 1), i32), "pos": sds((b,), i32),
                "table": sds((b, mb), i32), "cache": cache}


def build_model(cfg) -> Model:
    return Model(cfg)
