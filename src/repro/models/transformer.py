"""Decoder-only transformer LM — the workhorse for most of the registered
architectures (qwen1.5 / qwen3 / granite / minicpm3-MLA / qwen2-moe /
moonlight / kimi-k2 / internvl2 backbone).

Composable switches: GQA or MLA temporal mix, dense or MoE channel mix,
qkv-bias, qk-norm, sliding window, optional vision-stub prefix.  Layers are
scanned (stacked params) — HLO depth-independent.  DFA sees one segment
named "blocks"; with ``n_dense_layers`` (DeepSeek-V3's
``first_k_dense_replace``) a segment "dense" of dense-FFN blocks comes
first, and "blocks" holds the MoE layers after it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.photonics import forward_matmul
from repro.dist.sharding import annotate, unshard_fsdp
from repro.models.base import DFAModel, SavedSegment, SegmentSpec, cross_entropy_loss
from repro.nn.attention import Attention, MLAttention
from repro.nn.embeddings import Embedding
from repro.nn.frontends import VisionFrontendStub
from repro.nn.linear import GatedMLP, Linear
from repro.nn.module import Module, named_key, stack_init
from repro.nn.moe import COUNTERS as MOE_COUNTERS
from repro.nn.moe import MoE
from repro.nn.norms import RMSNorm


@dataclasses.dataclass(frozen=True)
class MoESettings:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared_experts: int = 0
    d_ff_shared: int | None = None
    experts_held: tuple[int, int] | None = None  # this chip's share (nn/moe.py)
    scoring: str = "softmax"  # softmax | sigmoid (noaux_tc selection bias)
    routed_scale: float = 1.0
    lb_weight: float = 0.01  # softmax routing's auxiliary losses
    z_weight: float = 1e-3


@dataclasses.dataclass(frozen=True)
class MLASettings:
    q_lora_rank: int | None = 768  # None: q projected straight from x
    kv_lora_rank: int = 256
    qk_nope_dim: int = 64
    qk_rope_dim: int = 32
    v_head_dim: int = 64


@dataclasses.dataclass(frozen=True)
class VisionSettings:
    d_vision: int = 1024
    n_patches: int = 256


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int  # all layers, the leading dense ones included
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    window: int | None = None
    moe: MoESettings | None = None
    mla: MLASettings | None = None
    vision: VisionSettings | None = None
    # leading layers with a dense d_ff FFN ahead of the MoE layers
    n_dense_layers: int = 0
    dtype: jnp.dtype = jnp.float32
    # attention chunking for long-sequence prefill
    q_chunk: int = 2048
    k_chunk: int = 1024
    # pad the embedding/unembedding vocab dim to a shard/MXU-aligned size;
    # odd vocabularies (e.g. 50280, 73448) otherwise fall back to unsharded
    # unembeddings whose logits all-reduce dominates the collective term
    pad_vocab_to: int | None = None

    @property
    def v_padded(self) -> int:
        return self.pad_vocab_to or self.vocab_size


@dataclasses.dataclass(frozen=True)
class DecoderBlock(Module):
    cfg: TransformerConfig
    dense: bool = False  # a dense FFN even where the model has MoE

    def _attn(self):
        c = self.cfg
        if c.mla is not None:
            m = c.mla
            return MLAttention(
                d_model=c.d_model, n_heads=c.n_heads,
                q_lora_rank=m.q_lora_rank, kv_lora_rank=m.kv_lora_rank,
                qk_nope_dim=m.qk_nope_dim, qk_rope_dim=m.qk_rope_dim,
                v_head_dim=m.v_head_dim, rope_theta=c.rope_theta,
                norm_eps=c.norm_eps, dtype=c.dtype,
            )
        return Attention(
            d_model=c.d_model, n_heads=c.n_heads, n_kv_heads=c.n_kv_heads,
            head_dim=c.head_dim, qkv_bias=c.qkv_bias, qk_norm=c.qk_norm,
            rope_theta=c.rope_theta, window=c.window, dtype=c.dtype,
        )

    @property
    def is_moe(self) -> bool:
        return self.cfg.moe is not None and not self.dense

    def _ffn(self):
        c = self.cfg
        if self.is_moe:
            m = c.moe
            return MoE(
                d_model=c.d_model, d_ff_expert=m.d_ff_expert,
                n_experts=m.n_experts, top_k=m.top_k,
                n_shared_experts=m.n_shared_experts, d_ff_shared=m.d_ff_shared,
                experts_held=m.experts_held, scoring=m.scoring,
                routed_scale=m.routed_scale, dtype=c.dtype,
            )
        return GatedMLP(c.d_model, c.d_ff, dtype=c.dtype)

    def _channel_mix(self, params, h):
        """-> (ffn(h), weighted aux loss, routing counters)."""
        if not self.is_moe:
            return self._ffn()(params, h), jnp.float32(0.0), {}
        m = self.cfg.moe
        h, aux = self._ffn()(params, h)
        aux_loss = jnp.float32(0.0)
        if "lb_loss" in aux:
            aux_loss = m.lb_weight * aux["lb_loss"] + m.z_weight * aux["z_loss"]
        stats = {f"moe_{k}": aux[k] for k in MOE_COUNTERS}
        return h, aux_loss, stats

    def init(self, key):
        c = self.cfg
        return {
            "norm1": RMSNorm(c.d_model, c.norm_eps, c.dtype).init(named_key(key, "norm1")),
            "attn": self._attn().init(named_key(key, "attn")),
            "norm2": RMSNorm(c.d_model, c.norm_eps, c.dtype).init(named_key(key, "norm2")),
            "ffn": self._ffn().init(named_key(key, "ffn")),
        }

    def forward(self, params, x, positions):
        """-> (y, weighted_aux_loss, routing counters ({} for dense))."""
        c = self.cfg
        norm = RMSNorm(c.d_model, c.norm_eps, c.dtype)
        h = norm(params["norm1"], x)
        h = self._attn()(params["attn"], h, positions=positions,
                         q_chunk=c.q_chunk, k_chunk=c.k_chunk)
        x = x + h
        h = norm(params["norm2"], x)
        h, aux_loss, stats = self._channel_mix(params["ffn"], h)
        y = annotate(x + h, "act_btd")
        return y, aux_loss, stats

    def __call__(self, params, x, positions):
        """-> (y, weighted_aux_loss)."""
        y, aux_loss, _ = self.forward(params, x, positions)
        return y, aux_loss

    # --- serving ---
    def init_cache(self, batch: int, max_len: int, dtype=None):
        return self._attn().init_cache(batch, max_len, dtype)

    def decode(self, params, x, cache, cache_len):
        c = self.cfg
        norm = RMSNorm(c.d_model, c.norm_eps, c.dtype)
        h = norm(params["norm1"], x)
        h, cache = self._attn().decode(params["attn"], h, cache, cache_len)
        x = x + h
        h, _, _ = self._channel_mix(params["ffn"], norm(params["norm2"], x))
        return x + h, cache

    def prefill(self, params, x, cache, cache_len, n_valid):
        """Chunked multi-token cache fill: x (B, C, d).  Padded (invalid)
        chunk positions still flow through the FFN; the MoE drops no token,
        so they change no valid position's output."""
        c = self.cfg
        norm = RMSNorm(c.d_model, c.norm_eps, c.dtype)
        h = norm(params["norm1"], x)
        h, cache = self._attn().prefill(params["attn"], h, cache, cache_len, n_valid)
        x = x + h
        h, _, _ = self._channel_mix(params["ffn"], norm(params["norm2"], x))
        return x + h, cache


@dataclasses.dataclass(frozen=True)
class TransformerLM(DFAModel):
    cfg: TransformerConfig

    @property
    def block(self) -> DecoderBlock:
        return DecoderBlock(self.cfg)

    def stacks(self) -> tuple[tuple[str, DecoderBlock, int], ...]:
        """(segment name, block, layers) of each scanned stack, in order:
        the leading dense layers ("dense", where there are any), then
        "blocks"."""
        c = self.cfg
        out = ((("dense", DecoderBlock(c, dense=True), c.n_dense_layers),)
               if c.n_dense_layers else ())
        return out + (("blocks", self.block, c.n_layers - c.n_dense_layers),)

    def counters(self) -> dict:
        if self.cfg.moe is None:
            return {}
        # the routing counters, averaged over the MoE layers
        return {"moe": tuple(f"moe_{k}" for k in MOE_COUNTERS)}

    @property
    def d_tap(self) -> int:
        return self.cfg.d_model  # "hidden" tap (DESIGN.md §8.3)

    def segment_specs(self):
        c = self.cfg

        def spec(name, block, n):
            def apply(p, x, extras):
                positions = extras
                return block(p, x, positions)

            return SegmentSpec(name, n, c.d_model, apply)

        return tuple(spec(*s) for s in self.stacks())

    def init(self, key):
        c = self.cfg
        embed = {"tok": Embedding(c.v_padded, c.d_model, c.dtype).init(named_key(key, "tok"))}
        if c.vision is not None:
            embed["vision"] = VisionFrontendStub(c.vision.d_vision, c.d_model, c.dtype).init(
                named_key(key, "vision")
            )
        params = {"embed": embed}
        for name, block, n in self.stacks():
            params[name] = stack_init(block, named_key(key, name), n)
        params["head"] = {
            "norm": RMSNorm(c.d_model, c.norm_eps, c.dtype).init(named_key(key, "fnorm")),
            "out": Linear(c.d_model, c.v_padded, dtype=c.dtype).init(named_key(key, "out")),
        }
        return params

    def embed(self, params, batch):
        c = self.cfg
        tok = Embedding(c.v_padded, c.d_model, c.dtype)(params["embed"]["tok"], batch["tokens"])
        if c.vision is not None and "patch_embeds" in batch:
            # vision prefix is optional: text-only prefill/serving is valid
            pre = VisionFrontendStub(c.vision.d_vision, c.d_model, c.dtype)(
                params["embed"]["vision"], batch["patch_embeds"]
            )
            tok = jnp.concatenate([pre.astype(tok.dtype), tok], axis=1)
        return annotate(tok, "act_btd")

    def run_segments(self, params, x0):
        b, s, _ = x0.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        saved, auxes = {}, {}
        x = x0
        for name, block, _ in self.stacks():
            def body(x, bp, block=block):
                bp = unshard_fsdp(bp)  # per-layer ZeRO-3 gather inside the scan
                y, aux, stats = block.forward(bp, x, positions)
                return y, (x, aux, stats)

            x, (inputs, aux, stats) = jax.lax.scan(body, x, params[name])
            inputs = annotate(inputs, "tape_lbsd")  # model-sharded DFA tape
            # routing counters: the mean over the segment's layers
            stats = {k: jnp.mean(v) for k, v in stats.items()} or None
            saved[name] = SavedSegment(inputs=inputs, extras=positions, stats=stats)
            auxes[name] = jnp.sum(aux)
        return x, saved, auxes

    def head_logits(self, params, x_final, batch):
        del batch
        c = self.cfg
        h = RMSNorm(c.d_model, c.norm_eps, c.dtype)(params["head"]["norm"], x_final)
        return annotate(self._head(params, h), "logits")

    def loss_from_logits(self, logits, batch):
        c = self.cfg
        if c.vision is not None:
            # loss only over the text region (after n_patches prefix)
            logits = logits[:, -batch["labels"].shape[1]:]
        mask = batch.get("mask")
        return cross_entropy_loss(logits, batch["labels"], mask=mask)

    # ---- serving ----------------------------------------------------------
    def init_caches(self, batch: int, max_len: int, dtype=None):
        """Per-layer caches of each stack, stacked (L leading axis), by
        segment name."""
        caches = {}
        for name, block, n in self.stacks():
            cache = block.init_cache(batch, max_len, dtype)
            caches[name] = jax.tree_util.tree_map(
                lambda x, n=n: jnp.broadcast_to(x[None], (n,) + x.shape).copy(), cache)
        return caches

    def _serve_stacks(self, params, x, caches, layer_fn):
        """Run ``layer_fn(block, bp, x, cache) -> (y, cache)`` over every
        stack; -> (x, new caches)."""
        new_caches = {}
        for name, block, _ in self.stacks():
            def body(x, xs, block=block):
                bp, cache = xs
                return layer_fn(block, unshard_fsdp(bp), x, cache)

            x, new_caches[name] = jax.lax.scan(body, x, (params[name], caches[name]))
        return x, new_caches

    def decode_step(self, params, token, caches, cache_len):
        """token: (B, 1) int. Returns (logits (B,1,V), new caches)."""
        c = self.cfg
        x = Embedding(c.v_padded, c.d_model, c.dtype)(params["embed"]["tok"], token)
        x, new_caches = self._serve_stacks(
            params, x, caches,
            lambda block, bp, x, cache: block.decode(bp, x, cache, cache_len))
        h = RMSNorm(c.d_model, c.norm_eps, c.dtype)(params["head"]["norm"], x)
        return self._head(params, h), new_caches

    def _head(self, params, h):
        """Unembedding with the same pad-vocab masking as ``head_logits`` —
        greedy serving must never emit a padding token id."""
        c = self.cfg
        logits = forward_matmul(h, params["head"]["out"]["w"])
        if c.pad_vocab_to:
            pad_mask = jnp.arange(c.v_padded) >= c.vocab_size
            logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype), logits)
        return logits

    @property
    def supports_parallel_prefill(self) -> bool:
        """Global-attention caches are absolute-indexed, so a whole prompt
        chunk can be scattered and attended in one forward; windowed
        (ring-buffer) variants must replay token-by-token."""
        return self.cfg.window is None

    def prefill_step(self, params, tokens, caches, cache_len, n_valid):
        """tokens (B, C) -> (logits (B, C, V), new caches).  ``cache_len``
        is NOT advanced here — the engine owns slot bookkeeping."""
        c = self.cfg
        x = Embedding(c.v_padded, c.d_model, c.dtype)(params["embed"]["tok"], tokens)
        x, new_caches = self._serve_stacks(
            params, x, caches,
            lambda block, bp, x, cache: block.prefill(bp, x, cache, cache_len, n_valid))
        h = RMSNorm(c.d_model, c.norm_eps, c.dtype)(params["head"]["norm"], x)
        return self._head(params, h), new_caches

    def forward_gemm_specs(self):
        """(name, m, k) of every weight-stationary forward projection of one
        token — the GEMMs ``photonics.forward_matmul`` routes, consumed by
        ``sim.pipeline.forward_workload``.  MoE counts router + the top-k
        (+ shared) expert FFNs actually streamed per token."""
        c = self.cfg
        hd = c.head_dim or c.d_model // c.n_heads
        attn = []
        if c.mla is not None:
            m = c.mla
            q_out = c.n_heads * (m.qk_nope_dim + m.qk_rope_dim)
            if m.q_lora_rank is None:
                attn.append(("attn.q", q_out, c.d_model))
            else:
                attn += [("attn.q_down", m.q_lora_rank, c.d_model),
                         ("attn.q_up", q_out, m.q_lora_rank)]
            attn += [
                ("attn.kv_down", m.kv_lora_rank + m.qk_rope_dim, c.d_model),
                ("attn.o", c.d_model, c.n_heads * m.v_head_dim),
            ]
        else:
            attn += [
                ("attn.q", c.n_heads * hd, c.d_model),
                ("attn.k", c.n_kv_heads * hd, c.d_model),
                ("attn.v", c.n_kv_heads * hd, c.d_model),
                ("attn.o", c.d_model, c.n_heads * hd),
            ]
        specs = []
        for name, block, n in self.stacks():
            per_layer = list(attn)
            ff = c.d_ff
            if block.is_moe:
                mo = c.moe
                ff = mo.top_k * mo.d_ff_expert
                if mo.n_shared_experts:
                    ff += mo.n_shared_experts * (mo.d_ff_shared or mo.d_ff_expert)
                per_layer.append(("ffn.router", mo.n_experts, c.d_model))
            per_layer += [
                ("ffn.gate", ff, c.d_model),
                ("ffn.up", ff, c.d_model),
                ("ffn.down", c.d_model, ff),
            ]
            for i in range(n):
                specs += [(f"{name}[{i}].{g}", m, k) for (g, m, k) in per_layer]
        specs.append(("head.unembed", c.v_padded, c.d_model))
        return specs
