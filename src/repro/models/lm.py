"""Unified LM covering all assigned families (dense/moe/ssm/hybrid/encdec/vlm).

Design:
  * layer params are stacked (leading L axis) and consumed by ``lax.scan`` —
    compile time is O(1) in depth, mandatory for 64L x 5120d dry-runs;
  * one ``layer_fn`` per family, selected statically from arch.family;
  * remat policy (none/selective/full — the paper's recompute-granularity)
    wraps the scan body;
  * decode uses per-layer caches threaded through the same scan as xs/ys;
    sliding-window archs (hymba) keep a ring-buffer KV of window size;
  * an interleaved model (``arch.layer_types``: Mamba-2 and attention layers
    by pattern) keeps one stack per kind of layer at ``layers/<kind>``, and
    each run of consecutive layers of one kind is a scan over its kind's
    stack; its caches are per kind too (KV for the attention layers, conv and
    SSD state for the Mamba ones), updated in place;
  * each layer kind's work, its pre-norm included, runs under a
    ``jax.named_scope`` (embed, attn, ffn, moe, ssd, head), which the
    compiled program keeps in its ops' metadata, so a profile can attribute
    device time to it in train, prefill and decode alike.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.arch import ModelArch
from repro.kernels import ops
from repro.models import layers as L
from repro.models.moe import aux_load_balance_loss, moe_block
from repro.models.ssm import CONV_K, ssm_block


@dataclasses.dataclass(frozen=True)
class ModelCfg:
    """Runtime (non-architectural) model options."""

    dtype: Any = jnp.bfloat16
    # "xla" | "pallas": the benchmark's modes pass both fields, so they stay;
    # every caller runs "xla"
    attn_impl: str = "xla"
    ssm_impl: str = "xla"
    remat: str = "none"  # none | selective | full  (paper recompute-granularity)
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _dense_init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


_ONES_LEAVES = ("ln1", "ln2", "ln_cross", "q_norm", "k_norm", "D")
_ZEROS_LEAVES = ("conv_b", "dt_bias", "A_log")


# the program's name of each kind of an interleaved model's layer: the scope
# its mixer runs under, and the key of its stacks in ``params["layers"]``
KINDS = {"attention": "attn", "mamba": "ssd"}
# the caches each kind of layer holds
KIND_CACHES = {"attn": ("k", "v"), "ssd": ("conv", "state")}


def layer_kinds(arch: ModelArch) -> list[str]:
    """The kind of each layer of an interleaved model, in order."""
    return [KINDS[t] for t in arch.layer_types]


def layer_runs(arch: ModelArch) -> list[tuple[str, int, int]]:
    """(kind, index within the kind of its first layer, count) of each run of
    consecutive layers of one kind of an interleaved model, in order."""
    runs: list[list] = []
    seen: dict[str, int] = {}
    for kind in layer_kinds(arch):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen.get(kind, 0), 1])
        seen[kind] = seen.get(kind, 0) + 1
    return [tuple(r) for r in runs]


def _scales(arch: ModelArch) -> tuple[float, float]:
    """(init scale of the input projections, of the output projections)."""
    fan = 1.0 / (arch.hidden ** 0.5)
    return fan, fan / (2.0 * max(arch.num_layers, 1)) ** 0.5


def _attn_templates(arch: ModelArch) -> dict:
    d, hd = arch.hidden, arch.head_dim
    H, Hkv = arch.heads, arch.kv_heads
    fan, out_scale = _scales(arch)
    t = {"attn.wqkv": ((d, (H + 2 * Hkv) * hd), fan), "attn.wo": ((H * hd, d), out_scale)}
    if arch.qk_norm:
        t["attn.q_norm"] = ((hd,), 0.0)
        t["attn.k_norm"] = ((hd,), 0.0)
    return t


def _ssm_templates(arch: ModelArch) -> dict:
    d = arch.hidden
    fan, out_scale = _scales(arch)
    di = arch.ssm_expand * d
    Hs = arch.ssm_heads or max(di // 64, 1)
    N = arch.ssm_state
    conv_dim = di + 2 * N
    t = {
        "ssm.in_proj": ((d, 2 * di + 2 * N + Hs), fan),
        "ssm.conv_w": ((CONV_K, conv_dim), 0.5),
        "ssm.conv_b": ((conv_dim,), 0.0),
        "ssm.dt_bias": ((Hs,), 0.0),
        "ssm.A_log": ((Hs,), 0.0),
        "ssm.D": ((Hs,), 0.0),
        "ssm.out_proj": ((di, d), out_scale),
    }
    if arch.ssm_gated_norm:
        # the gated RMSNorm's gain, named as the layer norms are
        t["ssm.ln1"] = ((di,), 0.0)
    return t


def _mlp_templates(arch: ModelArch) -> dict:
    fan, out_scale = _scales(arch)
    return {"mlp.wi": ((arch.hidden, 2 * arch.ffn), fan),
            "mlp.wo": ((arch.ffn, arch.hidden), out_scale)}


def _kind_templates(arch: ModelArch, kind: str) -> dict:
    """(shape, init_scale) per tensor of one layer of an interleaved model's
    ``kind``: its mixer, the MLP and the two norms every layer has."""
    t = _attn_templates(arch) if kind == "attn" else _ssm_templates(arch)
    t.update(_mlp_templates(arch))
    t["ln1"] = ((arch.hidden,), 0.0)
    t["ln2"] = ((arch.hidden,), 0.0)
    return t


def _layer_param_templates(arch: ModelArch) -> dict[str, tuple[tuple[int, ...], float]]:
    """(shape, init_scale) per per-layer tensor, WITHOUT the L axis.

    scale 0.0 marks constant-initialized leaves (ones for norms/D, zeros for
    biases/A_log)."""
    d, hd = arch.hidden, arch.head_dim
    H, Hkv = arch.heads, arch.kv_heads
    t: dict[str, tuple[tuple[int, ...], float]] = {}
    fan, out_scale = _scales(arch)
    if not arch.is_attention_free:
        t.update(_attn_templates(arch))
    if arch.family == "moe":
        F = arch.moe_ffn or arch.ffn
        t["moe.router"] = ((d, arch.num_experts), fan)
        t["moe.wi"] = ((arch.num_experts, d, 2 * F), fan)
        t["moe.wo"] = ((arch.num_experts, F, d), out_scale)
        if arch.shared_expert:
            t["moe.shared_wi"] = ((d, 2 * F), fan)
            t["moe.shared_wo"] = ((F, d), out_scale)
    elif arch.ffn > 0:
        t.update(_mlp_templates(arch))
    if arch.family in ("ssm", "hybrid"):
        t.update(_ssm_templates(arch))
    if arch.family == "encdec":
        t["cross.wq"] = ((d, H * hd), fan)
        t["cross.wkv"] = ((d, 2 * Hkv * hd), fan)
        t["cross.wo"] = ((H * hd, d), out_scale)
        t["ln_cross"] = ((d,), 0.0)
    t["ln1"] = ((d,), 0.0)
    if arch.family == "moe" or (arch.ffn > 0 and arch.family != "ssm"):
        t["ln2"] = ((d,), 0.0)
    return t


def _init_layer_stack(template: dict, key, n_layers: int, dtype) -> dict:
    out: dict[str, Any] = {}
    keys = jax.random.split(key, len(template))
    for (name, (shape, scale)), k in zip(sorted(template.items()), keys):
        full = (n_layers,) + shape
        leaf = name.rsplit(".", 1)[-1]
        if scale == 0.0:
            if leaf in _ONES_LEAVES:
                arr = jnp.ones(full, jnp.float32 if leaf in ("D",) else dtype)
            else:
                arr = jnp.zeros(full, jnp.float32 if leaf in _ZEROS_LEAVES else dtype)
        else:
            arr = _dense_init(k, full, scale, dtype)
        node = out
        *parents, last = name.split(".")
        for pkey in parents:
            node = node.setdefault(pkey, {})
        node[last] = arr
    return out


def init_params(arch: ModelArch, key, dtype=jnp.float32) -> dict:
    k_embed, k_layers, k_head, k_enc = jax.random.split(key, 4)
    d = arch.hidden
    if arch.layer_types:
        kinds = layer_kinds(arch)
        order = list(dict.fromkeys(kinds))
        layers = {kind: _init_layer_stack(_kind_templates(arch, kind), k, kinds.count(kind), dtype)
                  for kind, k in zip(order, jax.random.split(k_layers, len(order)))}
    else:
        layers = _init_layer_stack(_layer_param_templates(arch), k_layers, arch.num_layers, dtype)
    params: dict[str, Any] = {
        "embed": _dense_init(k_embed, (arch.vocab, d), 1.0 / (d ** 0.5), dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not arch.tie_embeddings:
        params["lm_head"] = _dense_init(k_head, (d, arch.vocab), 1.0 / (d ** 0.5), dtype)
    if arch.family == "encdec":
        enc_arch = dataclasses.replace(arch, family="dense", qk_norm=False)
        params["encoder"] = {
            "layers": _init_layer_stack(_layer_param_templates(enc_arch), k_enc,
                                        arch.encoder_layers, dtype),
            "final_norm": jnp.ones((d,), dtype),
        }
    return params


# ---------------------------------------------------------------------------
# sub-layers
# ---------------------------------------------------------------------------

def _norm(x, w, arch: ModelArch):
    return L.norm(x, w, eps=arch.rms_norm_eps)


def _scaled(x, m: float):
    """x * m rounded once to x's dtype; m = 1 leaves x as it is."""
    return x if m == 1.0 else (x.astype(jnp.float32) * m).astype(x.dtype)


def _cached_attention(q, k, v, start_pos, *, ring: bool = False, sm_scale=None):
    """Length-aware GQA attention against a (possibly partial) KV cache.

    q: (B, H, S, D) at absolute positions start_pos..start_pos+S-1;
    k/v: (B, Hkv, T, D). ``ring=True`` marks a wrap-around sliding cache:
    once start_pos >= T every slot is live. Scan-based online softmax —
    never materializes (S, T) logits (prefill_32k would need GiBs/head).
    """
    from repro.kernels.xla_flash import flash_xla

    S = q.shape[2]
    return flash_xla(q, k, v, q_start=start_pos, kv_valid_len=start_pos + S,
                     ring=ring, causal=True, sm_scale=sm_scale)


def _attn_sublayer(p, h, positions, arch: ModelArch, cfg: ModelCfg, cache,
                   window: int):
    """Self-attention. cache: None (training) or (k, v, start_pos)."""
    B, S, _ = h.shape
    H, Hkv, D = arch.heads, arch.kv_heads, arch.head_dim
    qkv = h @ p["wqkv"]
    q, k, v = jnp.split(qkv, [H * D, (H + Hkv) * D], axis=-1)
    q = q.reshape(B, S, H, D).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, Hkv, D).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, Hkv, D).transpose(0, 2, 1, 3)
    if arch.qk_norm:
        q = _norm(q, p["q_norm"], arch)
        k = _norm(k, p["k_norm"], arch)
    if arch.rope:
        q = L.rope(q, positions)
        k = L.rope(k, positions)
    scale = arch.attention_multiplier

    new_kv = None
    if cache is not None:
        ck, cv, start = cache
        T = ck.shape[2]
        if window and S >= T:
            # ring-cache prefill: banded attention over the fresh K/V, then
            # the cache keeps only the last `window` positions
            from repro.kernels.xla_flash import banded_flash_xla

            out = banded_flash_xla(q, k, v, window=window, sm_scale=scale)
            # ring invariant: slot j holds position p with p % T == j
            shift = (S - T) % T
            ck = jnp.roll(k[:, :, -T:], shift, axis=2).astype(ck.dtype)
            cv = jnp.roll(v[:, :, -T:], shift, axis=2).astype(cv.dtype)
        else:
            write_idx = start % T if window else start
            kq, vq = k.astype(ck.dtype), v.astype(cv.dtype)
            ck = jax.lax.dynamic_update_slice(ck, kq, (0, 0, write_idx, 0))
            cv = jax.lax.dynamic_update_slice(cv, vq, (0, 0, write_idx, 0))
            out = _cached_attention(q, ck, cv, start, ring=bool(window), sm_scale=scale)
        new_kv = {"k": ck, "v": cv}
    elif window and window < S:
        from repro.kernels.xla_flash import banded_flash_xla

        out = banded_flash_xla(q, k, v, window=window, sm_scale=scale)
    else:
        out = ops.flash_attention(q, k, v, causal=True, impl=cfg.attn_impl, sm_scale=scale)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * D)
    return out @ p["wo"], new_kv


def _cross_sublayer(p, h, enc_k, enc_v, arch: ModelArch, cfg: ModelCfg):
    B, S, _ = h.shape
    H, D = arch.heads, arch.head_dim
    q = (h @ p["wq"]).reshape(B, S, H, D).transpose(0, 2, 1, 3)
    out = ops.flash_attention(q, enc_k, enc_v, causal=False, impl="xla")
    return out.transpose(0, 2, 1, 3).reshape(B, S, H * D) @ p["wo"]


# ---------------------------------------------------------------------------
# one decoder layer (family-dispatched)
# ---------------------------------------------------------------------------

def _layer_fn(arch: ModelArch, cfg: ModelCfg, lp: dict, h, positions, cache,
              window: int):
    """cache: None (training) or dict with per-layer slices + 'len' scalar."""
    new_cache: dict[str, Any] = {}
    family = arch.family

    if family in ("dense", "moe", "vlm", "encdec"):
        with jax.named_scope("attn"):
            a, kv = _attn_sublayer(
                lp["attn"], _norm(h, lp["ln1"], arch),
                positions, arch, cfg,
                None if cache is None else (cache["k"], cache["v"], cache["len"]),
                window,
            )
        h = h + a
        if kv is not None:
            new_cache.update(kv)
        if family == "encdec":
            with jax.named_scope("attn"):
                c = _cross_sublayer(
                    lp["cross"], _norm(h, lp["ln_cross"], arch),
                    cache["enc_k"], cache["enc_v"], arch, cfg,
                )
            h = h + c
        if family == "moe":
            with jax.named_scope("moe"):
                m = moe_block(lp["moe"], _norm(h, lp["ln2"], arch),
                              top_k=arch.top_k, capacity_factor=cfg.capacity_factor)
        else:
            with jax.named_scope("ffn"):
                m = L.swiglu(lp["mlp"], _norm(h, lp["ln2"], arch))
        h = h + m

    elif family == "ssm":
        with jax.named_scope("ssd"):
            s, sc = ssm_block(
                lp["ssm"], _norm(h, lp["ln1"], arch), arch,
                ssm_impl=cfg.ssm_impl,
                cache=None if cache is None else (cache["conv"], cache["state"]),
            )
        h = h + s
        if sc is not None:
            new_cache["conv"], new_cache["state"] = sc

    elif family == "hybrid":
        # hymba: attention heads and mamba heads run in parallel on one input,
        # whose norm is counted with the attention
        with jax.named_scope("attn"):
            x_in = _norm(h, lp["ln1"], arch)
            a, kv = _attn_sublayer(
                lp["attn"], x_in, positions, arch, cfg,
                None if cache is None else (cache["k"], cache["v"], cache["len"]),
                window,
            )
        with jax.named_scope("ssd"):
            s, sc = ssm_block(
                lp["ssm"], x_in, arch, ssm_impl=cfg.ssm_impl,
                cache=None if cache is None else (cache["conv"], cache["state"]),
            )
        h = h + 0.5 * (a + s)
        if kv is not None:
            new_cache.update(kv)
        if sc is not None:
            new_cache["conv"], new_cache["state"] = sc
        with jax.named_scope("ffn"):
            h = h + L.swiglu(lp["mlp"], _norm(h, lp["ln2"], arch))

    else:
        raise ValueError(f"unknown family {family}")
    return h, new_cache


def _kind_layer(kind: str, arch: ModelArch, cfg: ModelCfg, lp: dict, h, positions,
                cache):
    """One layer of an interleaved model: ``h += r * mixer(norm(h))``, then
    ``h += r * swiglu(norm(h))``, the mixer GQA attention (``kind`` "attn") or
    the Mamba-2 mixer ("ssd"), r the arch's residual multiplier. cache: None
    (training) or the layer's slices of its kind's caches + 'len' scalar."""
    r = arch.residual_multiplier
    new_cache: dict[str, Any] = {}
    if kind == "attn":
        with jax.named_scope("attn"):
            a, kv = _attn_sublayer(
                lp["attn"], _norm(h, lp["ln1"], arch), positions, arch, cfg,
                None if cache is None else (cache["k"], cache["v"], cache["len"]),
                0,
            )
        if kv is not None:
            new_cache.update(kv)
    else:
        with jax.named_scope("ssd"):
            a, sc = ssm_block(
                lp["ssm"], _norm(h, lp["ln1"], arch), arch, ssm_impl=cfg.ssm_impl,
                cache=None if cache is None else (cache["conv"], cache["state"]),
            )
        if sc is not None:
            new_cache["conv"], new_cache["state"] = sc
    h = h + _scaled(a, r)
    with jax.named_scope("ffn"):
        m = L.swiglu(lp["mlp"], _norm(h, lp["ln2"], arch))
    return h + _scaled(m, r), new_cache


def _kind_runs_train(params, arch: ModelArch, cfg: ModelCfg, h, positions):
    """An interleaved model's layers in the pattern's order, without caches:
    each run of one kind is a scan over its slice of that kind's stacks."""
    from repro.parallel.sharding import constrain_batch_sharding

    for kind, first, n in layer_runs(arch):
        def body(carry, lp, kind=kind):
            carry = constrain_batch_sharding(carry)
            return _kind_layer(kind, arch, cfg, lp, carry, positions, None)[0], None

        if cfg.remat != "none":
            body = jax.checkpoint(body, policy=_remat_policy(cfg))
        stack = jax.tree_util.tree_map(lambda x: x[first:first + n], params["layers"][kind])
        h, _ = jax.lax.scan(body, h, stack)
    return h


def _kind_runs_cached(params, arch: ModelArch, cfg: ModelCfg, h, positions, caches: dict,
                      start_pos):
    """An interleaved model's layers in the pattern's order through its
    per-kind caches: each run of one kind is a scan over its layers' indices
    into that kind's stacks, reading each layer's slice of the kind's caches
    and writing it back in place. Returns (h, caches)."""
    caches = dict(caches)
    index = lambda x, i: jax.lax.dynamic_index_in_dim(x, i, keepdims=False)  # noqa: E731
    for kind, first, n in layer_runs(arch):
        stack = params["layers"][kind]
        names = KIND_CACHES[kind]

        def body(carry, i, kind=kind, stack=stack, names=names):
            hh, cc = carry
            lp = jax.tree_util.tree_map(lambda x: index(x, i), stack)
            layer_cache = {k: index(cc[k], i) for k in names}
            layer_cache["len"] = start_pos
            hh, new = _kind_layer(kind, arch, cfg, lp, hh, positions, layer_cache)
            cc = {k: jax.lax.dynamic_update_index_in_dim(cc[k], new[k].astype(cc[k].dtype), i, 0)
                  for k in names}
            return (hh, cc), None

        (h, sub), _ = jax.lax.scan(body, (h, {k: caches[k] for k in names}),
                                   jnp.arange(first, first + n))
        caches.update(sub)
    return h, caches


def _remat_policy(cfg: ModelCfg):
    if cfg.remat == "full":
        return jax.checkpoint_policies.nothing_saveable
    if cfg.remat == "selective":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


# ---------------------------------------------------------------------------
# forward (training / full-sequence)
# ---------------------------------------------------------------------------

def cast_params(params, dtype):
    """Mixed precision: fp32 master weights -> compute dtype once per step."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params,
    )


def _embed_inputs(params, arch: ModelArch, cfg: ModelCfg, batch: dict):
    tokens = batch["tokens"]
    with jax.named_scope("embed"):
        h = _scaled(params["embed"][tokens].astype(cfg.dtype), arch.embedding_multiplier)
        if arch.frontend_stub and "frontend" in batch:
            h = jnp.concatenate([batch["frontend"].astype(cfg.dtype), h], axis=1)
    positions = jnp.arange(h.shape[1])
    return h, positions


def _encode(params, arch: ModelArch, cfg: ModelCfg, features):
    """encdec: bidirectional encoder over stub frame embeddings (B, T, d)."""
    h = features.astype(cfg.dtype)
    B, T, _ = h.shape
    H, Hkv, D = arch.heads, arch.kv_heads, arch.head_dim
    positions = jnp.arange(T)

    def body(carry, lp):
        from repro.parallel.sharding import constrain_batch_sharding

        carry = constrain_batch_sharding(carry)
        with jax.named_scope("attn"):
            x_in = _norm(carry, lp["ln1"], arch)
            qkv = x_in @ lp["attn"]["wqkv"]
            q, k, v = jnp.split(qkv, [H * D, (H + Hkv) * D], axis=-1)
            q = L.rope(q.reshape(B, T, H, D).transpose(0, 2, 1, 3), positions)
            k = L.rope(k.reshape(B, T, Hkv, D).transpose(0, 2, 1, 3), positions)
            v = v.reshape(B, T, Hkv, D).transpose(0, 2, 1, 3)
            a = ops.flash_attention(q, k, v, causal=False, impl=cfg.attn_impl)
            a = a.transpose(0, 2, 1, 3).reshape(B, T, H * D) @ lp["attn"]["wo"]
        carry = carry + a
        with jax.named_scope("ffn"):
            m = L.swiglu(lp["mlp"], _norm(carry, lp["ln2"], arch))
        return carry + m, None

    if cfg.remat != "none":
        body = jax.checkpoint(body, policy=_remat_policy(cfg))
    h, _ = jax.lax.scan(body, h, params["encoder"]["layers"])
    return _norm(h, params["encoder"]["final_norm"], arch)


def _cross_kv(params, arch: ModelArch, enc_out):
    """Per-decoder-layer cross K/V from the encoder output: (L,B,Hkv,T,D) x2."""
    B, T, _ = enc_out.shape
    Hkv, D = arch.kv_heads, arch.head_dim

    def one_layer(wkv):
        kv = enc_out @ wkv
        k, v = jnp.split(kv, 2, axis=-1)
        return (k.reshape(B, T, Hkv, D).transpose(0, 2, 1, 3),
                v.reshape(B, T, Hkv, D).transpose(0, 2, 1, 3))

    return jax.vmap(one_layer)(params["layers"]["cross"]["wkv"])


def _head(params, arch: ModelArch, cfg: ModelCfg, h):
    with jax.named_scope("head"):
        h = _norm(h, params["final_norm"], arch)
        head = params["embed"].T if arch.tie_embeddings else params["lm_head"]
        logits = h @ head.astype(h.dtype)
        return logits if arch.logits_scaling == 1.0 else logits / arch.logits_scaling


def forward_logits(params, arch: ModelArch, cfg: ModelCfg, batch: dict):
    """Full-sequence forward. Returns (B, S_total, V) logits."""
    params = cast_params(params, cfg.dtype)
    h, positions = _embed_inputs(params, arch, cfg, batch)
    window = arch.sliding_window or 0
    if arch.layer_types:
        return _head(params, arch, cfg, _kind_runs_train(params, arch, cfg, h, positions))

    if arch.family == "encdec":
        enc_out = _encode(params, arch, cfg, batch["enc_features"])
        enc_k, enc_v = _cross_kv(params, arch, enc_out)  # (L, B, Hkv, T, D)
        xs_cache = {"enc_k": enc_k, "enc_v": enc_v}
    else:
        xs_cache = None

    def body(carry, xs):
        from repro.parallel.sharding import constrain_batch_sharding

        carry = constrain_batch_sharding(carry)
        lp, cc = xs
        if cc is not None:  # encdec: cross-attend to the encoder K/V
            hh, _ = _encdec_train_layer(arch, cfg, lp, carry, positions, cc, window)
            return hh, None
        hh, _ = _layer_fn(arch, cfg, lp, carry, positions, None, window)
        return hh, None

    if cfg.remat != "none":
        body = jax.checkpoint(body, policy=_remat_policy(cfg))
    h, _ = jax.lax.scan(body, h, (params["layers"], xs_cache))
    return _head(params, arch, cfg, h)


def _encdec_train_layer(arch, cfg, lp, h, positions, cc, window):
    with jax.named_scope("attn"):
        a, _ = _attn_sublayer(lp["attn"], _norm(h, lp["ln1"], arch),
                              positions, arch, cfg, None, window)
    h = h + a
    with jax.named_scope("attn"):
        c = _cross_sublayer(lp["cross"], _norm(h, lp["ln_cross"], arch),
                            cc["enc_k"], cc["enc_v"], arch, cfg)
    h = h + c
    with jax.named_scope("ffn"):
        h = h + L.swiglu(lp["mlp"], _norm(h, lp["ln2"], arch))
    return h, None


def forward_train(params, arch: ModelArch, cfg: ModelCfg, batch: dict):
    """Next-token CE loss (+ MoE aux loss). Returns (loss, metrics)."""
    logits = forward_logits(params, arch, cfg, batch)
    tokens = batch["tokens"]
    S_txt = tokens.shape[1]
    with jax.named_scope("head"):
        logits_txt = logits[:, -S_txt:, :]  # frontend positions carry no loss
        targets = tokens[:, 1:]
        lg = logits_txt[:, :-1, :].astype(jnp.float32)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
        nll = logz - gold
        mask = batch.get("loss_mask")
        if mask is not None:
            m = mask[:, 1:].astype(jnp.float32)
            loss = (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
        else:
            loss = nll.mean()
    metrics = {"ce_loss": loss}
    if arch.family == "moe" and cfg.moe_aux_weight > 0:
        h, _ = _embed_inputs(params, arch, cfg, batch)
        with jax.named_scope("moe"):
            aux = aux_load_balance_loss(
                jax.tree_util.tree_map(lambda x: x[0], params["layers"]["moe"]),
                h, top_k=arch.top_k,
            )
        metrics["aux_loss"] = aux
        loss = loss + cfg.moe_aux_weight * aux
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# serving: caches / prefill / decode
# ---------------------------------------------------------------------------

def _cache_layers(arch: ModelArch) -> tuple[int, int]:
    """(layers that hold a KV cache, layers that hold conv and SSD state)."""
    if arch.layer_types:
        kinds = layer_kinds(arch)
        return kinds.count("attn"), kinds.count("ssd")
    return (0 if arch.is_attention_free else arch.num_layers,
            arch.num_layers if arch.family in ("ssm", "hybrid") else 0)


def cache_layout(arch: ModelArch, cfg: ModelCfg, batch_size: int, max_len: int) -> dict:
    """Shape and dtype of each decode cache (the encoder's cross K/V of an
    encdec model left out): (L, B, ...) stacks over the layers that hold it."""
    n_kv, n_ssm = _cache_layers(arch)
    out: dict[str, jax.ShapeDtypeStruct] = {}
    if n_kv:
        kv_len = min(max_len, arch.sliding_window) if arch.sliding_window else max_len
        kv = jax.ShapeDtypeStruct((n_kv, batch_size, arch.kv_heads, kv_len, arch.head_dim),
                                  cfg.dtype)
        out["k"] = out["v"] = kv
    if n_ssm:
        di = arch.ssm_expand * arch.hidden
        H = arch.ssm_heads or max(di // 64, 1)
        conv_dim = di + 2 * arch.ssm_state
        out["conv"] = jax.ShapeDtypeStruct((n_ssm, batch_size, CONV_K - 1, conv_dim), cfg.dtype)
        out["state"] = jax.ShapeDtypeStruct(
            (n_ssm, batch_size, H, di // H, arch.ssm_state), jnp.float32)
    return out


def cache_bytes(arch: ModelArch, cfg: ModelCfg, batch_size: int, max_len: int) -> dict:
    """Bytes of each kind of decode cache: ``kv_bytes`` (K and V),
    ``ssm_state_bytes`` (SSD state) and ``conv_bytes`` (the convolution's
    last inputs)."""
    size = {k: s.size * s.dtype.itemsize
            for k, s in cache_layout(arch, cfg, batch_size, max_len).items()}
    return {"kv_bytes": sum(size.get(k, 0) for k in KIND_CACHES["attn"]),
            "ssm_state_bytes": size.get("state", 0), "conv_bytes": size.get("conv", 0)}


def prefill_ssd_segments(arch: ModelArch, seq_len: int) -> int:
    """Trips of the SSD's segment scan (``ops.ssd_with_state``) that a
    prefill of ``seq_len`` positions runs, over all its layers: one per
    ``arch.ssm_chunk`` positions in each layer that holds SSD state; none
    for one position, which is a step of the recurrence."""
    _, n_ssm = _cache_layers(arch)
    return n_ssm * -(-seq_len // arch.ssm_chunk) if seq_len > 1 else 0


def init_caches(arch: ModelArch, cfg: ModelCfg, batch_size: int, max_len: int,
                enc_features=None, params=None) -> dict:
    """Decode caches, each an (L, B, ...) stack over the layers that hold it
    (``cache_layout``)."""
    caches: dict[str, Any] = {k: jnp.zeros(s.shape, s.dtype) for k, s in
                              cache_layout(arch, cfg, batch_size, max_len).items()}
    if arch.family == "encdec":
        assert params is not None and enc_features is not None
        enc_out = _encode(params, arch, cfg, enc_features)
        caches["enc_k"], caches["enc_v"] = _cross_kv(params, arch, enc_out)
    return caches


def forward_cached(params, arch: ModelArch, cfg: ModelCfg, caches: dict,
                   tokens: jax.Array, start_pos, frontend=None, last_only: bool = False):
    """Shared prefill/decode path: processes S tokens starting at start_pos.
    ``last_only`` gives the logits of the last position alone, (B, 1, V)."""
    params = cast_params(params, cfg.dtype)
    start_pos = jnp.asarray(start_pos, jnp.int32)
    with jax.named_scope("embed"):
        h = _scaled(params["embed"][tokens].astype(cfg.dtype), arch.embedding_multiplier)
        if frontend is not None:
            h = jnp.concatenate([frontend.astype(cfg.dtype), h], axis=1)
    positions = start_pos + jnp.arange(h.shape[1])
    window = arch.sliding_window or 0

    if arch.layer_types:
        h, out = _kind_runs_cached(params, arch, cfg, h, positions, caches, start_pos)
    else:
        def body(carry, xs):
            lp, cc = xs
            cc = dict(cc)
            cc["len"] = start_pos
            hh, new_cache = _layer_fn(arch, cfg, lp, carry, positions, cc, window)
            return hh, new_cache

        h, new_caches = jax.lax.scan(body, h, (params["layers"], caches))
        out = dict(caches)
        out.update(new_caches)
    return _head(params, arch, cfg, h[:, -1:] if last_only else h), out


def prefill(params, arch, cfg, caches, tokens, frontend=None):
    """The prompt through every layer, filling the caches; returns the last
    position's logits, (B, 1, V), and the caches."""
    return forward_cached(params, arch, cfg, caches, tokens, 0, frontend=frontend,
                          last_only=True)


def decode_step(params, arch, cfg, caches, tokens, position):
    """tokens: (B, 1) new token ids; position: current sequence length."""
    return forward_cached(params, arch, cfg, caches, tokens, position)
