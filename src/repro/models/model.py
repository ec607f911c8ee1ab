"""Unified decoder-LM model zoo: config → init / loss / prefill / decode.

Families
--------
dense   llama-style GQA transformer (granite-20b, deepseek-67b, yi-9b,
        llama3.2-3b; also the backbone of qwen2-vl and musicgen)
moe     dense attention + MoE FFN (phi3.5-moe)
mla_moe DeepSeek-V2: MLA attention + shared+routed MoE, first layer dense
hybrid  Zamba2: Mamba2 backbone + weight-shared attention block every k layers
xlstm   mLSTM blocks with sLSTM blocks at configured positions
vlm     dense backbone + patch-embedding scatter (frontend stub) + M-RoPE
audio   dense backbone over K EnCodec codebooks (summed embeds, K heads)

Uniform stacks are ``lax.scan``-ed over stacked layer params (compile-time
O(1) in depth) with configurable remat; heterogeneous stacks (hybrid, xlstm)
are Python loops with per-layer remat.  Caches are ``Param``-boxed so the
dry-run can derive shapes *and* shardings without allocating.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import mla as MLA
from . import moe as MOE
from . import ssm as SSM
from . import xlstm as XL
from .common import KeyGen, Param, make_param, unbox


@dataclasses.dataclass
class ModelConfig:
    arch: str
    family: str                    # dense|moe|mla_moe|hybrid|xlstm|vlm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    moe_layer_start: int = 0       # layers < start use the dense FFN
    # MLA
    q_lora: int = 0
    kv_lora: int = 0
    nope_head_dim: int = 128
    rope_head_dim: int = 64
    v_head_dim: int = 128
    # SSM / hybrid
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 128
    ssd_decay_dtype: Any = jnp.float32   # bf16 = memory-term hillclimb lever
    attn_every: int = 0            # zamba2: shared attn block cadence
    # xLSTM
    slstm_every: int = 0           # 0 = no sLSTM layers; else layers i%k==1
    mlstm_chunk: int = 128
    # VLM
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    n_patches: int = 0
    # audio
    codebooks: int = 0
    # compute knobs (hillclimb levers)
    scan_layers: bool = True
    remat: bool = True
    remat_policy: str = "full"     # full | dots | none
    q_chunk: int = 2048
    kv_chunk: int = 2048
    unroll_attention: bool = False
    dtype: Any = jnp.bfloat16
    seq_shard_activations: bool = True

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.d_model // self.n_heads
        if self.d_ff_expert == 0 and self.n_experts:
            self.d_ff_expert = self.d_ff

    @property
    def is_attention_free(self) -> bool:
        return self.family == "xlstm"

    @property
    def masks_padding(self) -> bool:
        """Whether prefill and decode mask a left-padded prompt (per-row
        ``lengths``), so that prompts of different lengths share a batch."""
        return self.family in ("dense", "moe", "vlm", "audio")

    @property
    def supports_long_context(self) -> bool:
        return self.family in ("hybrid", "xlstm")

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS 6·N·D)."""
        shapes = jax.eval_shape(lambda: unbox(Model(self).init(jax.random.PRNGKey(0))))
        return sum(int(math.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))

    def active_param_count(self) -> int:
        """Params touched per token (= N_active for MoE rooflines)."""
        total = self.param_count()
        if not self.n_experts:
            return total
        per_expert = 3 * self.d_model * self.d_ff_expert
        n_moe_layers = self.n_layers - self.moe_layer_start
        inactive = per_expert * (self.n_experts - self.top_k) * n_moe_layers
        return total - inactive


def _remat(fn, cfg: ModelConfig):
    if not cfg.remat or cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _stack_init(init_one, keys: KeyGen, n: int):
    """vmap an init over layer keys; prepend the 'layers' logical axis."""
    ks = jnp.stack([keys() for _ in range(n)])
    stacked = jax.vmap(init_one)(ks)
    return jax.tree_util.tree_map(
        lambda p: Param(p.value, ("layers",) + p.axes),
        stacked, is_leaf=lambda x: isinstance(x, Param))


# =================================================================== Model ====
class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---------------------------------------------------------------- init ----
    def init(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        keys = KeyGen(rng)
        p: Dict[str, Any] = {}
        if cfg.family == "audio":
            p["embed"] = make_param(keys(), (cfg.codebooks, cfg.vocab, cfg.d_model),
                                    (None, "vocab", "embed"), scale=0.02)
            p["heads"] = make_param(keys(), (cfg.codebooks, cfg.d_model, cfg.vocab),
                                    (None, "embed", "vocab"), scale=cfg.d_model ** -0.5)
        else:
            p["embed"] = make_param(keys(), (cfg.vocab, cfg.d_model),
                                    ("vocab", "embed"), scale=0.02)
            p["lm_head"] = make_param(keys(), (cfg.d_model, cfg.vocab),
                                      ("embed", "vocab"), scale=cfg.d_model ** -0.5)
        p["final_norm"] = L.rms_norm_init(keys(), cfg.d_model)

        fam = cfg.family
        if fam in ("dense", "vlm", "audio"):
            p["layers"] = self._maybe_stack(self._dense_layer_init, keys, cfg.n_layers)
        elif fam == "moe":
            p["layers"] = self._maybe_stack(self._moe_layer_init, keys, cfg.n_layers)
        elif fam == "mla_moe":
            p["layer0"] = self._mla_dense_layer_init(keys())
            p["layers"] = self._maybe_stack(self._mla_moe_layer_init, keys,
                                            cfg.n_layers - 1)
        elif fam == "hybrid":
            p["shared_attn"] = self._shared_attn_init(keys)
            p["layers"] = {f"l{i}": self._mamba_layer_init(keys())
                           for i in range(cfg.n_layers)}
            n_shared = len(self._shared_sites())
            p["shared_proj"] = {
                f"s{i}": make_param(keys(), (2 * cfg.d_model, cfg.d_model),
                                    ("embed", "embed2"), scale=(2 * cfg.d_model) ** -0.5)
                for i in range(n_shared)}
        elif fam == "xlstm":
            p["layers"] = {}
            for i in range(cfg.n_layers):
                if self._is_slstm(i):
                    p["layers"][f"l{i}"] = {"norm": L.rms_norm_init(keys(), cfg.d_model),
                                            "slstm": XL.slstm_init(keys, cfg.d_model,
                                                                   cfg.n_heads)}
                else:
                    p["layers"][f"l{i}"] = {"norm": L.rms_norm_init(keys(), cfg.d_model),
                                            "mlstm": XL.mlstm_init(keys, cfg.d_model,
                                                                   cfg.n_heads,
                                                                   cfg.ssm_expand)}
        else:
            raise ValueError(fam)
        return p

    def _maybe_stack(self, init_one, keys: KeyGen, n: int):
        if self.cfg.scan_layers:
            return _stack_init(init_one, keys, n)
        return {f"l{i}": init_one(keys()) for i in range(n)}

    def _dense_layer_init(self, key):
        cfg = self.cfg
        keys = KeyGen(key)
        return {
            "ln1": L.rms_norm_init(keys(), cfg.d_model),
            "attn": L.gqa_init(keys, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim),
            "ln2": L.rms_norm_init(keys(), cfg.d_model),
            "mlp": L.mlp_init(keys, cfg.d_model, cfg.d_ff),
        }

    def _moe_layer_init(self, key):
        cfg = self.cfg
        keys = KeyGen(key)
        return {
            "ln1": L.rms_norm_init(keys(), cfg.d_model),
            "attn": L.gqa_init(keys, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim),
            "ln2": L.rms_norm_init(keys(), cfg.d_model),
            "moe": MOE.moe_init(keys, cfg.d_model, cfg.d_ff_expert, cfg.n_experts,
                                cfg.n_shared_experts),
        }

    def _mla_dense_layer_init(self, key):
        cfg = self.cfg
        keys = KeyGen(key)
        return {
            "ln1": L.rms_norm_init(keys(), cfg.d_model),
            "attn": MLA.mla_init(keys, cfg.d_model, cfg.n_heads, cfg.q_lora,
                                 cfg.kv_lora, cfg.nope_head_dim, cfg.rope_head_dim,
                                 cfg.v_head_dim),
            "ln2": L.rms_norm_init(keys(), cfg.d_model),
            "mlp": L.mlp_init(keys, cfg.d_model, cfg.d_ff_expert * 8),
        }

    def _mla_moe_layer_init(self, key):
        cfg = self.cfg
        keys = KeyGen(key)
        return {
            "ln1": L.rms_norm_init(keys(), cfg.d_model),
            "attn": MLA.mla_init(keys, cfg.d_model, cfg.n_heads, cfg.q_lora,
                                 cfg.kv_lora, cfg.nope_head_dim, cfg.rope_head_dim,
                                 cfg.v_head_dim),
            "ln2": L.rms_norm_init(keys(), cfg.d_model),
            "moe": MOE.moe_init(keys, cfg.d_model, cfg.d_ff_expert, cfg.n_experts,
                                cfg.n_shared_experts),
        }

    def _mamba_layer_init(self, key):
        cfg = self.cfg
        keys = KeyGen(key)
        return {
            "norm": L.rms_norm_init(keys(), cfg.d_model),
            "mamba": SSM.mamba2_init(keys, cfg.d_model, cfg.ssm_expand * cfg.d_model,
                                     cfg.ssm_state, cfg.ssm_headdim),
        }

    def _shared_attn_init(self, keys: KeyGen):
        cfg = self.cfg
        return {
            "ln1": L.rms_norm_init(keys(), cfg.d_model),
            "attn": L.gqa_init(keys, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim),
            "ln2": L.rms_norm_init(keys(), cfg.d_model),
            "mlp": L.mlp_init(keys, cfg.d_model, cfg.d_ff),
        }

    def _shared_sites(self):
        cfg = self.cfg
        if not cfg.attn_every:
            return []
        return [i for i in range(cfg.n_layers) if i % cfg.attn_every == 0]

    def _is_slstm(self, i: int) -> bool:
        return bool(self.cfg.slstm_every) and i % self.cfg.slstm_every == 1

    # ------------------------------------------------------------- embedding ----
    def _norm(self, p, x):
        """RMSNorm at the model's own epsilon."""
        return L.rms_norm(p, x, self.cfg.rms_eps)

    def _embed(self, params, batch):
        cfg = self.cfg
        if cfg.family == "audio":
            # tokens [B,K,S] → summed codebook embeddings
            toks = batch["tokens"]
            x = jnp.zeros((toks.shape[0], toks.shape[2], cfg.d_model), cfg.dtype)
            for kb in range(cfg.codebooks):
                x = x + jnp.take(params["embed"][kb], toks[:, kb], axis=0)
        else:
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
        if cfg.family == "vlm" and "patch_embeds" in batch:
            # modality stub: precomputed patch embeddings scattered over the
            # token sequence at patch_positions
            bidx = jnp.arange(x.shape[0])[:, None]
            x = x.at[bidx, batch["patch_positions"]].set(
                batch["patch_embeds"].astype(x.dtype))
        return L.lsc(x.astype(cfg.dtype), "batch", "seq", None)

    def _rope(self, batch, S, start=None):
        """RoPE angles for ``S`` positions; with ``start`` [B] each row's
        positions count from 0 at its first valid slot (left padding)."""
        cfg = self.cfg
        pos = jnp.arange(S)[None, :]
        if start is not None:
            pos = pos - start[:, None]
        if cfg.family == "vlm":
            if "positions3" in batch:
                pos3 = batch["positions3"]
            else:
                pos3 = jnp.broadcast_to(pos[..., None], pos.shape + (3,))
            return L.mrope_angles(pos3, cfg.head_dim, cfg.mrope_sections,
                                  cfg.rope_theta)
        return L.rope_angles(pos, cfg.head_dim, cfg.rope_theta)

    def _unembed(self, params, x):
        cfg = self.cfg
        x = self._norm(params["final_norm"], x)
        if cfg.family == "audio":
            logits = jnp.einsum("bsd,kdv->bskv", x, params["heads"])
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
        return logits.astype(jnp.float32)

    # ------------------------------------------------------------ forward ----
    def forward(self, params, batch):
        """Full-sequence forward → logits (params must be unboxed)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        S = x.shape[1]
        fam = cfg.family

        if fam in ("dense", "vlm", "audio", "moe", "mla_moe"):
            cos, sin = (self._rope(batch, S) if fam != "mla_moe" else (None, None))
            aux_total = jnp.zeros((), jnp.float32)

            if fam == "mla_moe":
                positions = jnp.arange(S)

                def block(x, lp):
                    h = MLA.mla_forward(lp["attn"], self._norm(lp["ln1"], x), positions,
                                        cfg.nope_head_dim, cfg.rope_head_dim,
                                        cfg.rope_theta, cfg.q_chunk, cfg.kv_chunk,
                                        unroll=cfg.unroll_attention, eps=cfg.rms_eps)
                    x = x + h
                    m, aux = MOE.moe_forward(lp["moe"], self._norm(lp["ln2"], x),
                                             cfg.top_k, cfg.capacity_factor)
                    x = x + m
                    x = L.lsc(x, "batch", "act_seq", None)
                    return x, aux

                def block0(x, lp):
                    h = MLA.mla_forward(lp["attn"], self._norm(lp["ln1"], x), positions,
                                        cfg.nope_head_dim, cfg.rope_head_dim,
                                        cfg.rope_theta, cfg.q_chunk, cfg.kv_chunk,
                                        unroll=cfg.unroll_attention, eps=cfg.rms_eps)
                    x = x + h
                    x = x + L.mlp_forward(lp["mlp"], self._norm(lp["ln2"], x))
                    return x

                x = _remat(block0, cfg)(x, params["layer0"])
                x, auxs = self._run_stack(block, x, params["layers"], cfg.n_layers - 1)
                aux_total = auxs
            elif fam == "moe":
                def block(x, lp):
                    h = L.gqa_forward(lp["attn"], self._norm(lp["ln1"], x), cos, sin,
                                      q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                                      unroll=cfg.unroll_attention)
                    x = x + h
                    m, aux = MOE.moe_forward(lp["moe"], self._norm(lp["ln2"], x),
                                             cfg.top_k, cfg.capacity_factor)
                    x = x + m
                    x = L.lsc(x, "batch", "act_seq", None)
                    return x, aux

                x, aux_total = self._run_stack(block, x, params["layers"], cfg.n_layers)
            else:
                def block(x, lp):
                    h = L.gqa_forward(lp["attn"], self._norm(lp["ln1"], x), cos, sin,
                                      q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                                      unroll=cfg.unroll_attention)
                    x = x + h
                    x = x + L.mlp_forward(lp["mlp"], self._norm(lp["ln2"], x))
                    x = L.lsc(x, "batch", "act_seq", None)
                    return x, jnp.zeros((), jnp.float32)

                x, aux_total = self._run_stack(block, x, params["layers"], cfg.n_layers)
            return self._unembed(params, x), aux_total

        if fam == "hybrid":
            x0 = x
            cos, sin = L.rope_angles(jnp.arange(S), cfg.head_dim, cfg.rope_theta)
            sites = self._shared_sites()
            site_no = 0
            for i in range(cfg.n_layers):
                lp = params["layers"][f"l{i}"]
                if i in sites:
                    x = self._shared_attn_apply(params, x, x0, site_no, cos, sin)
                    site_no += 1

                def mblock(x, lp=lp):
                    return x + SSM.mamba2_forward(lp["mamba"],
                                                  self._norm(lp["norm"], x),
                                                  cfg.ssm_chunk,
                                                  decay_dtype=cfg.ssd_decay_dtype,
                                                  eps=cfg.rms_eps)

                x = _remat(mblock, cfg)(x)
            return self._unembed(params, x), jnp.zeros((), jnp.float32)

        if fam == "xlstm":
            for i in range(cfg.n_layers):
                lp = params["layers"][f"l{i}"]
                if self._is_slstm(i):
                    def sblock(x, lp=lp):
                        return x + XL.slstm_forward(lp["slstm"],
                                                    self._norm(lp["norm"], x),
                                                    cfg.n_heads, eps=cfg.rms_eps)
                    x = _remat(sblock, cfg)(x)
                else:
                    def mblock(x, lp=lp):
                        return x + XL.mlstm_forward(lp["mlstm"],
                                                    self._norm(lp["norm"], x),
                                                    cfg.n_heads, cfg.mlstm_chunk,
                                                    eps=cfg.rms_eps)
                    x = _remat(mblock, cfg)(x)
            return self._unembed(params, x), jnp.zeros((), jnp.float32)

        raise ValueError(fam)

    def _shared_attn_apply(self, params, x, x0, site_no, cos, sin):
        """Zamba2 shared block: concat(x, embeddings) → proj → shared attn+mlp."""
        cfg = self.cfg
        sp = params["shared_attn"]
        proj = params["shared_proj"][f"s{site_no}"]

        def block(x):
            h = jnp.concatenate([x, x0], axis=-1) @ proj
            h = h + L.gqa_forward(sp["attn"], self._norm(sp["ln1"], h), cos, sin,
                                  q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                                  unroll=cfg.unroll_attention)
            h = h + L.mlp_forward(sp["mlp"], self._norm(sp["ln2"], h))
            return x + h

        return _remat(block, cfg)(x)

    def _run_stack(self, block, x, layer_params, n_layers):
        cfg = self.cfg
        if cfg.scan_layers:
            body = _remat(block, cfg)

            def scan_body(x, lp):
                return body(x, lp)

            x, auxs = jax.lax.scan(scan_body, x, layer_params)
            return x, jnp.sum(auxs)
        aux_total = jnp.zeros((), jnp.float32)
        body = _remat(block, cfg)
        for i in range(n_layers):
            x, aux = body(x, layer_params[f"l{i}"])
            aux_total = aux_total + aux
        return x, aux_total

    # ---------------------------------------------------------------- loss ----
    def loss(self, params, batch):
        logits, aux = self.forward(params, batch)
        targets = batch["targets"]
        if self.cfg.family == "audio":
            # logits [B,S,K,V], targets [B,K,S]
            targets = targets.transpose(0, 2, 1)
        mask = (targets >= 0).astype(jnp.float32)
        tgt = jnp.maximum(targets, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss + 0.01 * aux, {"nll": loss, "aux": aux}

    # -------------------------------------------------------------- prefill ----
    def init_cache(self, batch_size: int, max_len: int):
        """Boxed zero cache (axes drive the dry-run shardings)."""
        cfg = self.cfg
        fam = cfg.family
        dt = cfg.dtype
        if fam in ("dense", "vlm", "audio", "moe"):
            kv = (cfg.n_layers, batch_size, max_len, cfg.n_kv_heads, cfg.head_dim)
            axes = ("layers", "batch", "seq_kv", "kv_heads", None)
            # pos: the next slot, shared by the batch; start: each row's
            # first valid slot, after the left padding of a shorter prompt
            return {"k": Param(jnp.zeros(kv, dt), axes),
                    "v": Param(jnp.zeros(kv, dt), axes),
                    "pos": Param(jnp.zeros((), jnp.int32), ()),
                    "start": Param(jnp.zeros((batch_size,), jnp.int32), ("batch",))}
        if fam == "mla_moe":
            return {
                "ckv": Param(jnp.zeros((cfg.n_layers, batch_size, max_len, cfg.kv_lora), dt),
                             ("layers", "batch", "seq_kv", None)),
                "kr": Param(jnp.zeros((cfg.n_layers, batch_size, max_len, cfg.rope_head_dim), dt),
                            ("layers", "batch", "seq_kv", None)),
                "pos": Param(jnp.zeros((), jnp.int32), ()),
            }
        if fam == "hybrid":
            di = cfg.ssm_expand * cfg.d_model
            H = di // cfg.ssm_headdim
            n_sites = len(self._shared_sites())
            return {
                "ssm": Param(jnp.zeros((cfg.n_layers, batch_size, H, cfg.ssm_state,
                                        cfg.ssm_headdim), jnp.float32),
                             ("layers", "batch", None, None, None)),
                "conv": Param(jnp.zeros((cfg.n_layers, batch_size, 3, di), dt),
                              ("layers", "batch", None, "ffn")),
                "k": Param(jnp.zeros((n_sites, batch_size, max_len, cfg.n_kv_heads,
                                      cfg.head_dim), dt),
                           (None, "batch", "seq_kv", "kv_heads", None)),
                "v": Param(jnp.zeros((n_sites, batch_size, max_len, cfg.n_kv_heads,
                                      cfg.head_dim), dt),
                           (None, "batch", "seq_kv", "kv_heads", None)),
                "pos": Param(jnp.zeros((), jnp.int32), ()),
            }
        if fam == "xlstm":
            di = cfg.ssm_expand * cfg.d_model
            Dh = di // cfg.n_heads
            dh = cfg.d_model // cfg.n_heads
            n_s = sum(1 for i in range(cfg.n_layers) if self._is_slstm(i))
            n_m = cfg.n_layers - n_s
            return {
                "C": Param(jnp.zeros((n_m, batch_size, cfg.n_heads, Dh, Dh), jnp.float32),
                           ("layers", "batch", None, None, None)),
                "n": Param(jnp.zeros((n_m, batch_size, cfg.n_heads, Dh), jnp.float32),
                           ("layers", "batch", None, None)),
                "s_h": Param(jnp.zeros((max(n_s, 1), 3, batch_size, cfg.n_heads, dh),
                                       jnp.float32),
                             ("layers", None, "batch", None, None)),
                "pos": Param(jnp.zeros((), jnp.int32), ()),
            }
        raise ValueError(fam)

    # -------------------------------------------------------------- decode ----
    def decode(self, params, cache, batch):
        """One decode step: batch['tokens'] [B,1] (audio: [B,K,1]).
        Returns (logits, new_cache).  params/cache unboxed."""
        cfg = self.cfg
        fam = cfg.family
        pos = cache["pos"]
        x = self._embed_decode(params, batch)
        B = x.shape[0]

        if fam in ("dense", "vlm", "audio", "moe"):
            start = cache["start"]
            posb = (pos - start)[:, None]
            if fam == "vlm":
                pos3 = jnp.broadcast_to(posb[..., None], (B, 1, 3))
                cos, sin = L.mrope_angles(pos3, cfg.head_dim, cfg.mrope_sections,
                                          cfg.rope_theta)
            else:
                cos, sin = L.rope_angles(posb, cfg.head_dim, cfg.rope_theta)

            if cfg.scan_layers:
                def body(x, lp_and_cache):
                    lp, ck, cv = lp_and_cache
                    h, ck, cv = self._decode_block(lp, x, ck, cv, pos, cos, sin, start)
                    return h, (ck, cv)

                x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                                     cache["v"]))
                cache = {**cache, "k": ks, "v": vs, "pos": pos + 1}
            else:
                ks, vs = [], []
                for i in range(cfg.n_layers):
                    lp = params["layers"][f"l{i}"]
                    x, ck, cv = self._decode_block(lp, x, cache["k"][i], cache["v"][i],
                                                   pos, cos, sin, start)
                    ks.append(ck)
                    vs.append(cv)
                cache = {**cache, "k": jnp.stack(ks), "v": jnp.stack(vs), "pos": pos + 1}
            return self._unembed(params, x)[:, -1], cache

        if fam == "mla_moe":
            def mla_block(x, lp, ckv, kr, dense_mlp):
                h, ckv, kr = MLA.mla_decode(lp["attn"], self._norm(lp["ln1"], x),
                                            ckv, kr, pos, cfg.nope_head_dim,
                                            cfg.rope_head_dim, cfg.rope_theta,
                                            eps=cfg.rms_eps)
                x = x + h
                if dense_mlp:
                    x = x + L.mlp_forward(lp["mlp"], self._norm(lp["ln2"], x))
                else:
                    m, _ = MOE.moe_forward(lp["moe"], self._norm(lp["ln2"], x),
                                           cfg.top_k, cfg.capacity_factor)
                    x = x + m
                return x, ckv, kr

            x, ckv0, kr0 = mla_block(x, params["layer0"], cache["ckv"][0],
                                     cache["kr"][0], True)

            def body(x, lp_and_cache):
                lp, ckv, kr = lp_and_cache
                x, ckv, kr = mla_block(x, lp, ckv, kr, False)
                return x, (ckv, kr)

            if cfg.scan_layers:
                x, (ckvs, krs) = jax.lax.scan(
                    body, x, (params["layers"], cache["ckv"][1:], cache["kr"][1:]))
            else:
                outs = []
                for i in range(cfg.n_layers - 1):
                    x, out = body(x, (params["layers"][f"l{i}"],
                                      cache["ckv"][1 + i], cache["kr"][1 + i]))
                    outs.append(out)
                ckvs = jnp.stack([o[0] for o in outs])
                krs = jnp.stack([o[1] for o in outs])
            cache = {**cache,
                     "ckv": jnp.concatenate([ckv0[None], ckvs]),
                     "kr": jnp.concatenate([kr0[None], krs]),
                     "pos": pos + 1}
            return self._unembed(params, x)[:, -1], cache

        if fam == "hybrid":
            x0 = x
            posb = jnp.full((B, 1), pos, jnp.int32)
            cos, sin = L.rope_angles(posb, cfg.head_dim, cfg.rope_theta)
            sites = self._shared_sites()
            site_no = 0
            ssm_states, conv_states = [], []
            ks, vs = list(cache["k"]), list(cache["v"])
            for i in range(cfg.n_layers):
                lp = params["layers"][f"l{i}"]
                if i in sites:
                    sp = params["shared_attn"]
                    proj = params["shared_proj"][f"s{site_no}"]
                    h = jnp.concatenate([x, x0], axis=-1) @ proj
                    a, ks[site_no], vs[site_no] = L.gqa_decode(
                        sp["attn"], self._norm(sp["ln1"], h), ks[site_no], vs[site_no],
                        pos, cos, sin)
                    h = h + a
                    h = h + L.mlp_forward(sp["mlp"], self._norm(sp["ln2"], h))
                    x = x + h
                    site_no += 1
                out, s, cc = SSM.mamba2_decode(lp["mamba"], self._norm(lp["norm"], x),
                                               cache["ssm"][i], cache["conv"][i],
                                               eps=cfg.rms_eps)
                x = x + out
                ssm_states.append(s)
                conv_states.append(cc)
            cache = {"ssm": jnp.stack(ssm_states), "conv": jnp.stack(conv_states),
                     "k": jnp.stack(ks), "v": jnp.stack(vs), "pos": pos + 1}
            return self._unembed(params, x)[:, -1], cache

        if fam == "xlstm":
            Cs, ns, shs = [], [], []
            mi = si = 0
            for i in range(cfg.n_layers):
                lp = params["layers"][f"l{i}"]
                if self._is_slstm(i):
                    st = tuple(cache["s_h"][si])
                    out, st = XL.slstm_decode(lp["slstm"], self._norm(lp["norm"], x),
                                              st, cfg.n_heads, eps=cfg.rms_eps)
                    shs.append(jnp.stack(st))
                    si += 1
                else:
                    out, (C, n) = XL.mlstm_decode(lp["mlstm"],
                                                  self._norm(lp["norm"], x),
                                                  (cache["C"][mi], cache["n"][mi]),
                                                  cfg.n_heads, eps=cfg.rms_eps)
                    Cs.append(C)
                    ns.append(n)
                    mi += 1
                x = x + out
            cache = {"C": jnp.stack(Cs), "n": jnp.stack(ns),
                     "s_h": jnp.stack(shs) if shs else cache["s_h"],
                     "pos": pos + 1}
            return self._unembed(params, x)[:, -1], cache

        raise ValueError(fam)

    def _decode_block(self, lp, x, ck, cv, pos, cos, sin, start):
        cfg = self.cfg
        h, ck, cv = L.gqa_decode(lp["attn"], self._norm(lp["ln1"], x), ck, cv, pos,
                                 cos, sin, start)
        x = x + h
        if "mlp" in lp:
            x = x + L.mlp_forward(lp["mlp"], self._norm(lp["ln2"], x))
        else:
            m, _ = MOE.moe_forward(lp["moe"], self._norm(lp["ln2"], x),
                                   cfg.top_k, cfg.capacity_factor)
            x = x + m
        return x, ck, cv

    def _embed_decode(self, params, batch):
        cfg = self.cfg
        if cfg.family == "audio":
            toks = batch["tokens"]  # [B,K,1]
            x = jnp.zeros((toks.shape[0], 1, cfg.d_model), cfg.dtype)
            for kb in range(cfg.codebooks):
                x = x + jnp.take(params["embed"][kb], toks[:, kb], axis=0)
            return x
        return jnp.take(params["embed"], batch["tokens"], axis=0).astype(cfg.dtype)

    # ------------------------------------------------------------- prefill ----
    def prefill(self, params, batch, max_len: Optional[int] = None):
        """Forward over the prompt, returning (last_logits, populated cache).

        For the attention families the K/V computed during the forward pass are
        written into a fresh cache; SSM/xLSTM families return final states.

        ``batch["lengths"]`` [B] (default: all ``S``) gives each row's prompt
        length, its prompt left-padded to ``S``.  The attention families
        (``cfg.masks_padding``) mask each row's padding in prefill and every
        later decode step, and count its positions from its first token, so
        a row's logits do not depend on the rest of its batch; the others
        take no padding."""
        cfg = self.cfg
        fam = cfg.family
        S = batch["tokens"].shape[-1]
        B = batch["tokens"].shape[0]
        max_len = max_len or S
        cache = unbox(self.init_cache(B, max_len))

        if fam in ("dense", "vlm", "audio", "moe"):
            start = S - batch.get("lengths", jnp.full((B,), S, jnp.int32))
            x = self._embed(params, batch)
            cos, sin = self._rope(batch, S, start)
            ks, vs = [], []

            def block(x, lp):
                h, (k, v) = L.gqa_forward(lp["attn"], self._norm(lp["ln1"], x), cos,
                                          sin, q_chunk=cfg.q_chunk,
                                          kv_chunk=cfg.kv_chunk, return_kv=True,
                                          unroll=cfg.unroll_attention, kv_start=start)
                x = x + h
                if "mlp" in lp:
                    x = x + L.mlp_forward(lp["mlp"], self._norm(lp["ln2"], x))
                else:
                    m, _ = MOE.moe_forward(lp["moe"], self._norm(lp["ln2"], x),
                                           cfg.top_k, cfg.capacity_factor)
                    x = x + m
                x = L.lsc(x, "batch", "act_seq", None)
                return x, (k.astype(cfg.dtype), v.astype(cfg.dtype))

            if cfg.scan_layers:
                x, (ks, vs) = jax.lax.scan(_remat(block, cfg), x, params["layers"])
            else:
                kl, vl = [], []
                for i in range(cfg.n_layers):
                    x, (k, v) = _remat(block, cfg)(x, params["layers"][f"l{i}"])
                    kl.append(k)
                    vl.append(v)
                ks, vs = jnp.stack(kl), jnp.stack(vl)
            pad = max_len - S
            if pad:
                ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            cache.update({"k": ks, "v": vs, "pos": jnp.asarray(S, jnp.int32),
                          "start": start.astype(jnp.int32)})
            return self._unembed(params, x)[:, -1], cache

        if fam == "mla_moe":
            x = self._embed(params, batch)
            positions = jnp.arange(S)
            ckvs, krs = [], []

            def block(x, lp, dense_mlp):
                h, (ckv, kr) = MLA.mla_forward(
                    lp["attn"], self._norm(lp["ln1"], x), positions,
                    cfg.nope_head_dim, cfg.rope_head_dim, cfg.rope_theta,
                    cfg.q_chunk, cfg.kv_chunk, return_cache=True,
                    unroll=cfg.unroll_attention, eps=cfg.rms_eps)
                x = x + h
                if dense_mlp:
                    x = x + L.mlp_forward(lp["mlp"], self._norm(lp["ln2"], x))
                else:
                    m, _ = MOE.moe_forward(lp["moe"], self._norm(lp["ln2"], x),
                                           cfg.top_k, cfg.capacity_factor)
                    x = x + m
                return x, (ckv.astype(cfg.dtype), kr.astype(cfg.dtype))

            x, (ckv0, kr0) = _remat(partial(block, dense_mlp=True), cfg)(
                x, params["layer0"])

            def body(x, lp):
                return _remat(partial(block, dense_mlp=False), cfg)(x, lp)

            if cfg.scan_layers:
                x, (ckvs, krs) = jax.lax.scan(body, x, params["layers"])
            else:
                outs = []
                for i in range(cfg.n_layers - 1):
                    x, out = body(x, params["layers"][f"l{i}"])
                    outs.append(out)
                ckvs = jnp.stack([o[0] for o in outs])
                krs = jnp.stack([o[1] for o in outs])
            ckvs = jnp.concatenate([ckv0[None], ckvs])
            krs = jnp.concatenate([kr0[None], krs])
            pad = max_len - S
            if pad:
                ckvs = jnp.pad(ckvs, ((0, 0), (0, 0), (0, pad), (0, 0)))
                krs = jnp.pad(krs, ((0, 0), (0, 0), (0, pad), (0, 0)))
            cache.update({"ckv": ckvs, "kr": krs, "pos": jnp.asarray(S, jnp.int32)})
            return self._unembed(params, x)[:, -1], cache

        if fam == "hybrid":
            x = self._embed(params, batch)
            x0 = x
            cos, sin = L.rope_angles(jnp.arange(S), cfg.head_dim, cfg.rope_theta)
            sites = self._shared_sites()
            site_no = 0
            ssm_states, conv_states, ks, vs = [], [], [], []
            for i in range(cfg.n_layers):
                lp = params["layers"][f"l{i}"]
                if i in sites:
                    sp = params["shared_attn"]
                    proj = params["shared_proj"][f"s{site_no}"]
                    h = jnp.concatenate([x, x0], axis=-1) @ proj
                    a, (k, v) = L.gqa_forward(sp["attn"], self._norm(sp["ln1"], h),
                                              cos, sin, q_chunk=cfg.q_chunk,
                                              kv_chunk=cfg.kv_chunk, return_kv=True,
                                              unroll=cfg.unroll_attention)
                    h = h + a
                    h = h + L.mlp_forward(sp["mlp"], self._norm(sp["ln2"], h))
                    x = x + h
                    pad = max_len - S
                    k, v = k.astype(cfg.dtype), v.astype(cfg.dtype)
                    if pad:
                        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    ks.append(k)
                    vs.append(v)
                    site_no += 1
                out, (s, cc) = SSM.mamba2_forward(lp["mamba"],
                                                  self._norm(lp["norm"], x),
                                                  cfg.ssm_chunk, return_state=True,
                                                  decay_dtype=cfg.ssd_decay_dtype,
                                                  eps=cfg.rms_eps)
                x = x + out
                ssm_states.append(s)
                conv_states.append(cc.astype(cfg.dtype))
            cache.update({"ssm": jnp.stack(ssm_states), "conv": jnp.stack(conv_states),
                          "k": jnp.stack(ks), "v": jnp.stack(vs),
                          "pos": jnp.asarray(S, jnp.int32)})
            return self._unembed(params, x)[:, -1], cache

        if fam == "xlstm":
            x = self._embed(params, batch)
            Cs, ns, shs = [], [], []
            for i in range(cfg.n_layers):
                lp = params["layers"][f"l{i}"]
                if self._is_slstm(i):
                    out, st = XL.slstm_forward(lp["slstm"], self._norm(lp["norm"], x),
                                               cfg.n_heads, return_state=True,
                                               eps=cfg.rms_eps)
                    shs.append(jnp.stack(st))
                else:
                    out, (C, n) = XL.mlstm_forward(lp["mlstm"],
                                                   self._norm(lp["norm"], x),
                                                   cfg.n_heads, cfg.mlstm_chunk,
                                                   return_state=True, eps=cfg.rms_eps)
                    Cs.append(C)
                    ns.append(n)
                x = x + out
            cache.update({"C": jnp.stack(Cs), "n": jnp.stack(ns),
                          "pos": jnp.asarray(S, jnp.int32)})
            if shs:
                cache["s_h"] = jnp.stack(shs)
            return self._unembed(params, x)[:, -1], cache

        raise ValueError(fam)
