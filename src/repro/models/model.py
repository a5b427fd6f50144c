"""LanguageModel: assembles blocks into the full architecture.

Layer-stack decomposition for compile-time economy: the layer list is
factored into  [prefix | R × super-block | tail]  where the super-block is
the smallest repeating (kind, is_moe) period — the R repeats lower as a
single `lax.scan` (one HLO body regardless of depth). Heterogeneous
interleaves (gemma3 5local:1global, jamba 7mamba:1attn, llama4
dense/MoE alternation) are super-blocks.

Execution modes: `forward` (train), `prefill` (emits KV/recurrent cache),
`decode_step` (one token against the cache). Audio (whisper) runs an
encoder over stub frame embeddings with decoder cross-attention; VLM
(paligemma) prepends projected stub patch embeddings.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RWKV, MAMBA
from repro.models import blocks as blk
from repro.models.attention import AttnOpts
from repro.models.layers import (init_norm, apply_norm, init_embed,
                                 embed_tokens, unembed, dense_init)


@jax.custom_vjp
def _sequence_barrier(x):
    """Identity with an XLA optimization barrier in both the forward
    and backward pass. `jax.lax.optimization_barrier` has no AD rule,
    but the layer-wise ZeRO-3 loop needs one inside `value_and_grad`:
    without it XLA hoists every block's all-gather ahead of the loop
    and re-creates the whole-vector live peak the per-block partition
    exists to avoid."""
    return jax.lax.optimization_barrier(x)


def _sequence_barrier_fwd(x):
    return _sequence_barrier(x), None


def _sequence_barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_sequence_barrier.defvjp(_sequence_barrier_fwd, _sequence_barrier_bwd)


@dataclasses.dataclass(frozen=True)
class ModelOpts:
    dtype: str = "bfloat16"
    remat: bool = True
    use_kernels: bool = False
    block_k: int = 512
    n_q_chunks: int = 8
    # mesh axes the batch dim of activations is sharded over; when set,
    # a with_sharding_constraint re-anchors the (B,S,d) carry inside the
    # layer scan — XLA otherwise loses the sharding in the rematted
    # backward body and replicates the whole carry (§Perf finding)
    act_batch_axes: tuple = ()

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)


def _lcm(a, b):
    return a * b // math.gcd(a, b)


class LanguageModel:
    def __init__(self, cfg: ModelConfig, opts: ModelOpts = ModelOpts()):
        self.cfg = cfg
        self.opts = opts
        self.attn_opts = AttnOpts(dtype=opts.jdtype, block_k=opts.block_k,
                                  n_q_chunks=opts.n_q_chunks,
                                  use_kernels=opts.use_kernels)
        self.gelu_mlp = cfg.family == "audio"
        self.has_cross = cfg.enc_layers > 0
        pat = cfg.pattern()
        self.specs = [(pat[i], cfg.is_moe_layer(i))
                      for i in range(cfg.n_layers)]
        # stack decomposition
        self.prefix_len = cfg.moe.first_dense if cfg.moe else 0
        period = len(cfg.layer_pattern)
        if cfg.moe:
            period = _lcm(period, cfg.moe.every)
        rem = cfg.n_layers - self.prefix_len
        self.period = period
        self.repeats = rem // period
        self.tail_len = rem - self.repeats * period
        self.stack_specs = self.specs[self.prefix_len:
                                      self.prefix_len + period]

    # ------------------------------------------------------------- init
    def init(self, key) -> dict:
        cfg = self.cfg
        kE, kP, kS, kT, kN, kEnc, kProj = jax.random.split(key, 7)
        params = {"embed": init_embed(cfg, kE),
                  "final_norm": init_norm(cfg)}
        if self.prefix_len:
            params["prefix"] = [
                blk.init_block(cfg, jax.random.fold_in(kP, i),
                               self.specs[i][0], self.specs[i][1],
                               self.has_cross, self.gelu_mlp)
                for i in range(self.prefix_len)]

        def init_superblock(k):
            return {f"t{t}": blk.init_block(
                cfg, jax.random.fold_in(k, t), self.stack_specs[t][0],
                self.stack_specs[t][1], self.has_cross, self.gelu_mlp)
                for t in range(self.period)}

        if self.repeats:
            params["stack"] = jax.vmap(init_superblock)(
                jax.random.split(kS, self.repeats))
        if self.tail_len:
            base = self.prefix_len + self.repeats * self.period
            params["tail"] = [
                blk.init_block(cfg, jax.random.fold_in(kT, i),
                               self.specs[base + i][0],
                               self.specs[base + i][1],
                               self.has_cross, self.gelu_mlp)
                for i in range(self.tail_len)]
        if cfg.enc_layers:
            def init_enc_block(k):
                return blk.init_block(cfg, k, "attn", False, False,
                                      gelu_mlp=True)
            params["enc"] = {
                "stack": jax.vmap(init_enc_block)(
                    jax.random.split(kEnc, cfg.enc_layers)),
                "final_norm": init_norm(cfg),
                "pos": 0.02 * jax.random.normal(
                    jax.random.fold_in(kEnc, 99),
                    (cfg.enc_tokens, cfg.d_model), jnp.float32),
            }
        if cfg.frontend == "vision_stub":
            fd = cfg.frontend_dim or cfg.d_model
            params["projector"] = dense_init(kProj, (fd, cfg.d_model))
        return params

    # --------------------------------------------------------- encoder
    def encode(self, params, frames):
        """Whisper encoder over stub frame embeddings (B, Te, d)."""
        cfg = self.cfg
        x = frames.astype(self.opts.jdtype) + \
            params["enc"]["pos"].astype(self.opts.jdtype)

        def body(x, layer_params):
            y, _, _ = blk.apply_block_seq(
                cfg, layer_params, "attn", False, x, jnp.int32(0),
                self.attn_opts, gelu_mlp=True, causal=False)
            return y, None

        x, _ = jax.lax.scan(body, x, params["enc"]["stack"])
        return apply_norm(params["enc"]["final_norm"], x, cfg.norm_eps)

    # ------------------------------------------------------ seq runner
    def _run_seq(self, params, x, pos0, enc_out, cache_capacity):
        cfg, opts = self.cfg, self.attn_opts
        aux = jnp.zeros((), jnp.float32)
        caches = {}

        def one(params_i, x, spec, cap):
            return blk.apply_block_seq(
                cfg, params_i, spec[0], spec[1], x, pos0, opts,
                cache_capacity=cap, enc_out=enc_out,
                gelu_mlp=self.gelu_mlp)

        if self.prefix_len:
            pc = []
            for i in range(self.prefix_len):
                x, c, a = one(params["prefix"][i], x, self.specs[i],
                              cache_capacity)
                pc.append(c)
                aux = aux + a
            caches["prefix"] = pc

        if self.repeats:
            def sb_body(carry, sb_params):
                x, aux = carry
                if self.opts.act_batch_axes:
                    from jax.sharding import PartitionSpec
                    x = jax.lax.with_sharding_constraint(
                        x, PartitionSpec(tuple(self.opts.act_batch_axes),
                                         None, None))
                cs = {}
                for t in range(self.period):
                    x, c, a = one(sb_params[f"t{t}"], x,
                                  self.stack_specs[t], cache_capacity)
                    cs[f"t{t}"] = c
                    aux = aux + a
                return (x, aux), cs

            body = sb_body
            if self.opts.remat and not cache_capacity:
                body = jax.checkpoint(sb_body, prevent_cse=False)
            stack = params["stack"]
            if isinstance(stack, (list, tuple)):
                # layer-wise ZeRO-3: the superblocks arrive as a list
                # of per-block pytrees (each typically the all-gather
                # of one 1/N chunk). Run them unrolled so each gather
                # is consumed and dropped before the next block's
                # params materialize; the optimization barrier ties
                # block r's params to block r-1's output, so XLA
                # cannot hoist every gather ahead of the loop and
                # re-create the whole-vector peak.
                carry, sc = (x, aux), []
                for r, sb_params in enumerate(stack):
                    if r:
                        sb_params, carry = _sequence_barrier(
                            (sb_params, carry))
                    carry, cs = body(carry, sb_params)
                    sc.append(cs)
                x, aux = carry
                if cache_capacity:
                    caches["stack"] = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs), *sc)
            else:
                (x, aux), sc = jax.lax.scan(body, (x, aux), stack)
                if cache_capacity:
                    caches["stack"] = sc

        if self.tail_len:
            base = self.prefix_len + self.repeats * self.period
            tc = []
            for i in range(self.tail_len):
                x, c, a = one(params["tail"][i], x, self.specs[base + i],
                              cache_capacity)
                tc.append(c)
                aux = aux + a
            caches["tail"] = tc
        return x, caches, aux

    # ------------------------------------------------------- frontends
    def _prepend_frontend(self, params, x, frontend):
        """VLM: project + prepend patch embeddings. Returns (x, n_prefix)."""
        cfg = self.cfg
        if cfg.frontend == "vision_stub":
            fe = jnp.einsum("bpd,de->bpe", frontend.astype(self.opts.jdtype),
                            params["projector"].astype(self.opts.jdtype))
            return jnp.concatenate([fe, x], axis=1), cfg.frontend_tokens
        return x, 0

    # ------------------------------------------------------------ train
    def forward(self, params, tokens, frontend=None):
        """Train-mode forward. Returns (logits, aux_loss)."""
        cfg = self.cfg
        x = embed_tokens(params["embed"], tokens, cfg, self.opts.jdtype)
        enc_out = None
        if cfg.enc_layers:
            enc_out = self.encode(params, frontend)
        n_prefix = 0
        if cfg.frontend == "vision_stub":
            x, n_prefix = self._prepend_frontend(params, x, frontend)
        x, _, aux = self._run_seq(params, x, jnp.int32(0), enc_out, 0)
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = unembed(params["embed"], x, cfg)
        if n_prefix:
            logits = logits[:, n_prefix:]
        return logits, aux

    def loss(self, params, batch):
        """Next-token cross-entropy (+ MoE aux). batch: {'tokens': (B,S),
        optional 'frontend'}."""
        tokens = batch["tokens"]
        logits, aux = self.forward(params, tokens[:, :-1],
                                   batch.get("frontend"))
        targets = tokens[:, 1:]
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(
            logits.astype(jnp.float32), targets[..., None],
            axis=-1)[..., 0]
        ce = jnp.mean(lse - picked)
        return ce + aux, {"ce": ce, "aux": aux}

    # ---------------------------------------------------------- prefill
    def prefill(self, params, tokens, frontend=None,
                cache_capacity: Optional[int] = None):
        """Returns (last-token logits, cache)."""
        cfg = self.cfg
        S = tokens.shape[1]
        cap = cache_capacity or S + 1  # one free slot for the next token
        x = embed_tokens(params["embed"], tokens, cfg, self.opts.jdtype)
        enc_out = None
        if cfg.enc_layers:
            enc_out = self.encode(params, frontend)
        n_prefix = 0
        if cfg.frontend == "vision_stub":
            x, n_prefix = self._prepend_frontend(params, x, frontend)
            cap = cap + n_prefix
        x, caches, _ = self._run_seq(params, x, jnp.int32(0), enc_out, cap)
        x = apply_norm(params["final_norm"], x[:, -1:], cfg.norm_eps)
        logits = unembed(params["embed"], x, cfg)
        return logits, caches

    # ------------------------------------------------------ decode step
    def decode_step(self, params, token, cache, pos):
        """token: (B,1) int32; pos: scalar int32 — absolute position of
        this token (for the assigned decode shapes, pos == context len and
        every cache is full). Returns (logits (B,1,V), new cache)."""
        cfg, opts = self.cfg, self.attn_opts
        x = embed_tokens(params["embed"], token, cfg, self.opts.jdtype)
        aux = jnp.zeros((), jnp.float32)
        new_cache = {}

        def one(params_i, x, spec, c):
            return blk.apply_block_decode(cfg, params_i, spec[0], spec[1],
                                          x, c, pos, opts,
                                          gelu_mlp=self.gelu_mlp)

        if self.prefix_len:
            pc = []
            for i in range(self.prefix_len):
                x, c, a = one(params["prefix"][i], x, self.specs[i],
                              cache["prefix"][i])
                pc.append(c)
            new_cache["prefix"] = pc

        if self.repeats:
            def sb_body(carry, xs):
                x = carry
                sbp, sbc = xs
                cs = {}
                for t in range(self.period):
                    x, c, _ = one(sbp[f"t{t}"], x, self.stack_specs[t],
                                  sbc[f"t{t}"])
                    cs[f"t{t}"] = c
                return x, cs

            x, sc = jax.lax.scan(sb_body, x,
                                 (params["stack"], cache["stack"]))
            new_cache["stack"] = sc

        if self.tail_len:
            base = self.prefix_len + self.repeats * self.period
            tc = []
            for i in range(self.tail_len):
                x, c, a = one(params["tail"][i], x, self.specs[base + i],
                              cache["tail"][i])
                tc.append(c)
            new_cache["tail"] = tc

        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = unembed(params["embed"], x, cfg)
        return logits, new_cache

    # ---------------------------------------------------- cache builder
    def make_cache(self, batch: int, capacity: int):
        """Zero cache with the exact structure decode_step expects."""
        cfg = self.cfg
        dt = self.opts.jdtype

        def entry(spec):
            return blk.init_cache(cfg, spec[0], batch, capacity, dt,
                                  has_cross=self.has_cross,
                                  enc_tokens=cfg.enc_tokens)

        cache = {}
        if self.prefix_len:
            cache["prefix"] = [entry(self.specs[i])
                               for i in range(self.prefix_len)]
        if self.repeats:
            sb = {f"t{t}": entry(self.stack_specs[t])
                  for t in range(self.period)}
            cache["stack"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (self.repeats,) + a.shape),
                sb)
        if self.tail_len:
            base = self.prefix_len + self.repeats * self.period
            cache["tail"] = [entry(self.specs[base + i])
                             for i in range(self.tail_len)]
        return cache

    # ------------------------------------------------------ input specs
    def input_specs(self, shape_cfg):
        """ShapeDtypeStruct stand-ins for every model input of the given
        assigned shape (no allocation). Returns a dict of kwargs for the
        corresponding step function."""
        cfg = self.cfg
        B, S = shape_cfg.global_batch, shape_cfg.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        specs = {}
        if shape_cfg.mode == "train":
            specs["batch"] = {"tokens": sds((B, S + 1), i32)}
            if cfg.frontend == "vision_stub":
                fd = cfg.frontend_dim or cfg.d_model
                specs["batch"]["frontend"] = sds(
                    (B, cfg.frontend_tokens, fd), jnp.float32)
            if cfg.frontend == "audio_stub":
                specs["batch"]["frontend"] = sds(
                    (B, cfg.enc_tokens, cfg.d_model), jnp.float32)
        elif shape_cfg.mode == "prefill":
            specs["tokens"] = sds((B, S), i32)
            if cfg.frontend == "vision_stub":
                fd = cfg.frontend_dim or cfg.d_model
                specs["frontend"] = sds((B, cfg.frontend_tokens, fd),
                                        jnp.float32)
            if cfg.frontend == "audio_stub":
                specs["frontend"] = sds((B, cfg.enc_tokens, cfg.d_model),
                                        jnp.float32)
        else:  # decode
            cap = S + 1 + (cfg.frontend_tokens
                           if cfg.frontend == "vision_stub" else 0)
            specs["token"] = sds((B, 1), i32)
            specs["cache"] = jax.eval_shape(
                lambda: self.make_cache(B, cap))
            specs["pos"] = sds((), i32)
        return specs


def build_model(name_or_cfg, opts: ModelOpts = ModelOpts(),
                reduced: bool = False) -> LanguageModel:
    from repro.configs.base import get_config, ModelConfig as MC
    cfg = (name_or_cfg if isinstance(name_or_cfg, MC)
           else get_config(name_or_cfg))
    if reduced:
        cfg = cfg.reduced()
    return LanguageModel(cfg, opts)
