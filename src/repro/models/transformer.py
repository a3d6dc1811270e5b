"""Generic LM assembly: embed → segments of scanned homogeneous layers → head.

Layer kinds: attn_mlp (dense), attn_moe, mamba2, mamba2_mlp (granite's
Mamba-2 mixer followed by the MLP), mlstm, slstm, shared_attn (zamba2's
weight-tied attention blocks — 2 alternating sets).

The class exposes the decomposed interface SmartFreeze's progressive trainer
needs: ``embed`` / ``run_layers(lo, hi)`` / ``head``, where run_layers slices
stacked scan parameters at arbitrary (static) layer boundaries so a freeze
block never has to align with a segment.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import activation, dense, dense_init, norm, norm_init
from repro.models.module import (PFac, Params, axes_to_tree, init_stack,
                                 slice_stack)


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------


def mlp_init(fac: PFac, cfg: ArchConfig, d_ff: int) -> Params:
    d = cfg.d_model
    return {"gate": dense_init(fac, "gate", d, d_ff, (None, "mlp")),
            "up": dense_init(fac, "up", d, d_ff, (None, "mlp")),
            "down": dense_init(fac, "down", d_ff, d, ("mlp", None))}


def mlp_apply(p: Params, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    act = activation(cfg.mlp_activation)
    return dense(p["down"], act(dense(p["gate"], x)) * dense(p["up"], x))


def _residual(y: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """A residual branch scaled by ``cfg.residual_multiplier``."""
    r = cfg.residual_multiplier
    return y if r == 1.0 else y * jnp.asarray(r, y.dtype)


def layer_init(fac: PFac, cfg: ArchConfig, kind: str) -> Params:
    p: Params = {}
    if kind == "mamba2_mlp":
        p["ln1"] = norm_init(fac, "ln1", cfg.d_model, cfg.norm)
        p["mix"] = ssm_mod.mamba2_init(fac.sub("mix"), cfg)
        p["ln2"] = norm_init(fac, "ln2", cfg.d_model, cfg.norm)
        p["mlp"] = mlp_init(fac.sub("mlp"), cfg, cfg.d_ff)
    elif kind in ("attn_mlp", "attn_moe", "shared_attn"):
        p["ln1"] = norm_init(fac, "ln1", cfg.d_model, cfg.norm)
        p["attn"] = attn.attn_init(fac.sub("attn"), cfg)
        p["ln2"] = norm_init(fac, "ln2", cfg.d_model, cfg.norm)
        if kind == "attn_moe":
            p["moe"] = moe_mod.moe_init(fac.sub("moe"), cfg)
        else:
            p["mlp"] = mlp_init(fac.sub("mlp"), cfg, cfg.d_ff)
    elif kind == "mamba2":
        p["ln"] = norm_init(fac, "ln", cfg.d_model, cfg.norm)
        p["mix"] = ssm_mod.mamba2_init(fac.sub("mix"), cfg)
    elif kind == "mlstm":
        p["ln"] = norm_init(fac, "ln", cfg.d_model, cfg.norm)
        p["mix"] = ssm_mod.mlstm_init(fac.sub("mix"), cfg)
    elif kind == "slstm":
        p["ln"] = norm_init(fac, "ln", cfg.d_model, cfg.norm)
        p["mix"] = ssm_mod.slstm_init(fac.sub("mix"), cfg)
    else:
        raise ValueError(kind)
    return p


def layer_apply(p: Params, x: jnp.ndarray, cfg: ArchConfig, kind: str, *,
                causal: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-seq layer. Returns (y, aux_loss)."""
    aux = jnp.float32(0.0)
    if kind == "mamba2_mlp":
        h = x + _residual(ssm_mod.mamba2_forward(
            p["mix"], norm(p["ln1"], x, cfg.norm, cfg.norm_eps), cfg), cfg)
        y = mlp_apply(p["mlp"], norm(p["ln2"], h, cfg.norm, cfg.norm_eps), cfg)
        return h + _residual(y, cfg), aux
    if kind in ("attn_mlp", "attn_moe", "shared_attn"):
        h = x + _residual(attn.attn_forward(
            p["attn"], norm(p["ln1"], x, cfg.norm, cfg.norm_eps), cfg,
            causal=causal), cfg)
        hn = norm(p["ln2"], h, cfg.norm, cfg.norm_eps)
        if kind == "attn_moe":
            y, aux = moe_mod.moe_forward(p["moe"], hn, cfg)
        else:
            y = mlp_apply(p["mlp"], hn, cfg)
        return h + _residual(y, cfg), aux
    # ssm/recurrent kinds
    fn = {"mamba2": ssm_mod.mamba2_forward, "mlstm": ssm_mod.mlstm_forward,
          "slstm": ssm_mod.slstm_forward}[kind]
    return x + fn(p["mix"], norm(p["ln"], x, cfg.norm, cfg.norm_eps), cfg), aux


def layer_init_cache(cfg: ArchConfig, kind: str, batch: int, max_seq: int, dtype):
    if kind in ("attn_mlp", "attn_moe", "shared_attn"):
        return attn.attn_init_cache(cfg, batch, max_seq, dtype)
    fn = {"mamba2": ssm_mod.mamba2_init_state,
          "mamba2_mlp": ssm_mod.mamba2_init_state,
          "mlstm": ssm_mod.mlstm_init_state,
          "slstm": ssm_mod.slstm_init_state}[kind]
    return fn(cfg, batch, dtype)


def layer_decode(p: Params, x: jnp.ndarray, cache, pos, cfg: ArchConfig, kind: str):
    if kind == "mamba2_mlp":
        a, cache = ssm_mod.mamba2_step(
            p["mix"], norm(p["ln1"], x, cfg.norm, cfg.norm_eps), cache, cfg)
        h = x + _residual(a, cfg)
        y = mlp_apply(p["mlp"], norm(p["ln2"], h, cfg.norm, cfg.norm_eps), cfg)
        return h + _residual(y, cfg), cache
    if kind in ("attn_mlp", "attn_moe", "shared_attn"):
        a, cache = attn.attn_decode(p["attn"], norm(p["ln1"], x, cfg.norm, cfg.norm_eps),
                                    cache, pos, cfg)
        h = x + _residual(a, cfg)
        hn = norm(p["ln2"], h, cfg.norm, cfg.norm_eps)
        if kind == "attn_moe":
            y = moe_mod.moe_decode(p["moe"], hn, cfg)
        else:
            y = mlp_apply(p["mlp"], hn, cfg)
        return h + _residual(y, cfg), cache
    fn = {"mamba2": ssm_mod.mamba2_step, "mlstm": ssm_mod.mlstm_step,
          "slstm": ssm_mod.slstm_step}[kind]
    y, cache = fn(p["mix"], norm(p["ln"], x, cfg.norm, cfg.norm_eps), cache, cfg)
    return x + y, cache


# ---------------------------------------------------------------------------
# The LM
# ---------------------------------------------------------------------------


@dataclass
class LM:
    cfg: ArchConfig

    # ----- construction -----

    def _build(self, fac: PFac) -> Params:
        cfg = self.cfg
        p: Params = {}
        p["embed"] = fac.param("embed", (cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"), init="embed", scale=0.02)
        if cfg.modality == "vision_stub":
            ff = fac.sub("frontend")
            p["frontend"] = {
                "proj1": dense_init(ff, "proj1", cfg.frontend_dim, cfg.d_model,
                                    (None, "embed")),
                "proj2": dense_init(ff, "proj2", cfg.d_model, cfg.d_model,
                                    ("embed", None)),
            }
        elif cfg.modality == "audio_stub":
            ff = fac.sub("frontend")
            p["frontend"] = {
                "proj": dense_init(ff, "proj", cfg.frontend_dim, cfg.d_model,
                                   (None, "embed")),
            }
        segf = fac.sub("segments")
        segs: Params = {}
        for i, (kind, n) in enumerate(cfg.segments()):
            if kind == "shared_attn":
                segs[str(i)] = {}  # weights live in p["shared_attn"]
            else:
                segs[str(i)] = init_stack(segf.sub(str(i)), n,
                                          lambda f, k=kind: layer_init(f, cfg, k))
        p["segments"] = segs
        if any(k == "shared_attn" for k, _ in cfg.segments()):
            nsets = max(cfg.num_shared_attn_sets, 1)
            saf = fac.sub("shared_attn")
            p["shared_attn"] = {str(j): layer_init(saf.sub(str(j)), cfg, "shared_attn")
                                for j in range(nsets)}
        p["final_norm"] = norm_init(fac, "final_norm", cfg.d_model, cfg.norm)
        if not cfg.tie_embeddings:
            p["head"] = dense_init(fac, "head", cfg.d_model, cfg.vocab_size,
                                   ("embed", "vocab"))
        return p

    def init(self, rng) -> Params:
        fac = PFac(rng, dtype=_dt(self.cfg.param_dtype))
        params = self._build(fac)
        self._axes_store = dict(fac.axes_store)
        return params

    def abstract_params(self) -> Params:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def axes_tree(self) -> Dict:
        if not hasattr(self, "_axes_store"):
            self.abstract_params()  # traces init, records axes
        tree = axes_to_tree(self._axes_store)
        # shared_attn segments own no params: mirror their empty dicts so the
        # axes tree has the SAME pytree structure as the param tree
        segs = tree.setdefault("segments", {})
        for i, (kind, n) in enumerate(self.cfg.segments()):
            if kind == "shared_attn":
                segs.setdefault(str(i), {})
        return tree

    # ----- segment bookkeeping -----

    def _seg_table(self) -> List[Tuple[str, int, int, int]]:
        """List of (kind, seg_index, layer_lo, layer_hi)."""
        out, lo = [], 0
        for i, (kind, n) in enumerate(self.cfg.segments()):
            out.append((kind, i, lo, lo + n))
            lo += n
        return out

    def _shared_attn_index(self, layer_idx: int) -> int:
        """Which tied weight set the shared-attn occurrence at layer_idx uses."""
        occ = 0
        for j, k in enumerate(self.cfg.layer_kinds()):
            if j == layer_idx:
                break
            if k == "shared_attn":
                occ += 1
        nsets = max(self.cfg.num_shared_attn_sets, 1)
        return occ % nsets

    # ----- forward pieces -----

    def embed(self, params: Params, batch: Dict) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.modality == "audio_stub":
            return dense(params["frontend"]["proj"],
                         batch["frames"].astype(_dt(cfg.compute_dtype)))
        tok = params["embed"]
        h = tok[batch["tokens"]].astype(_dt(cfg.compute_dtype))
        if cfg.embedding_multiplier != 1.0:
            h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
        if cfg.modality == "vision_stub" and "patches" in batch:
            fp = params["frontend"]
            pe = dense(fp["proj2"], jax.nn.gelu(
                dense(fp["proj1"], batch["patches"].astype(h.dtype))))
            h = jnp.concatenate([pe, h], axis=1)
        return h

    def run_layers(self, params: Params, h: jnp.ndarray, lo: int, hi: int,
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Run layers [lo, hi) full-sequence. Returns (h, aux_loss)."""
        cfg = self.cfg
        causal = not cfg.is_encoder_only
        aux = jnp.float32(0.0)
        for kind, si, s_lo, s_hi in self._seg_table():
            a, b = max(lo, s_lo), min(hi, s_hi)
            if a >= b:
                continue
            if kind == "shared_attn":
                sp = params["shared_attn"][str(self._shared_attn_index(s_lo))]
                h, al = layer_apply(sp, h, cfg, kind, causal=causal)
                aux = aux + al
            else:
                sliced = slice_stack(params["segments"][str(si)], a - s_lo, b - s_lo)

                def body(carry, lp, k=kind):
                    hh, ax = carry
                    hh, al = layer_apply(lp, hh, cfg, k, causal=causal)
                    return (hh, ax + al), None

                (h, aux), _ = jax.lax.scan(body, (h, aux), sliced)
        return h, aux

    def head(self, params: Params, h: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        h = norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = h @ params["embed"].T.astype(h.dtype)
        else:
            logits = dense(params["head"], h)
        return scale_logits(logits, cfg)

    def forward(self, params: Params, batch: Dict) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Full forward. Returns (logits, aux_loss)."""
        from repro.dist.sharding import shard_batch

        h = shard_batch(self.embed(params, batch), batch_axes=self.cfg.batch_axes)
        h, aux = self.run_layers(params, h, 0, self.cfg.num_layers)
        return self.head(params, h), aux

    def loss(self, params: Params, batch: Dict) -> jnp.ndarray:
        """Chunked-CE loss: never materializes [B, S, V] logits."""
        from repro.dist.sharding import shard_batch

        cfg = self.cfg
        h = shard_batch(self.embed(params, batch), batch_axes=self.cfg.batch_axes)
        h, aux = self.run_layers(params, h, 0, cfg.num_layers)
        h = norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
        head_w = params["embed"].T if cfg.tie_embeddings else params["head"]["w"]
        return chunked_ce_loss(h, head_w, batch, cfg) + 0.01 * aux

    # ----- decode -----

    def init_cache(self, batch: int, max_seq: int) -> Dict:
        cfg = self.cfg
        dtype = _dt(cfg.compute_dtype)
        caches = {}
        for kind, si, s_lo, s_hi in self._seg_table():
            if kind == "shared_attn":
                caches[str(si)] = layer_init_cache(cfg, kind, batch, max_seq, dtype)
            else:
                one = jax.eval_shape(
                    lambda k=kind: layer_init_cache(cfg, k, batch, max_seq, dtype))
                n = s_hi - s_lo
                caches[str(si)] = jax.tree.map(
                    lambda sd: jnp.zeros((n,) + sd.shape, sd.dtype), one)
        return caches

    def decode_step(self, params: Params, batch: Dict, cache: Dict, pos
                    ) -> Tuple[jnp.ndarray, Dict]:
        """One-token decode. batch['tokens']: [B, 1]. Returns (logits, cache)."""
        from repro.dist.sharding import shard_batch

        cfg = self.cfg
        h = shard_batch(self.embed(params, batch), batch_axes=cfg.batch_axes)
        new_caches = {}
        for kind, si, s_lo, s_hi in self._seg_table():
            if kind == "shared_attn":
                sp = params["shared_attn"][str(self._shared_attn_index(s_lo))]
                h, c = layer_decode(sp, h, cache[str(si)], pos, cfg, kind)
                new_caches[str(si)] = c
            else:
                def body(hh, xs, k=kind):
                    lp, lc = xs
                    hh, c = layer_decode(lp, hh, lc, pos, cfg, k)
                    return hh, c

                h, c = jax.lax.scan(body, h, (params["segments"][str(si)], cache[str(si)]))
                new_caches[str(si)] = c
        return self.head(params, h), new_caches


def _dt(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def scale_logits(logits: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """Logits divided by ``cfg.logits_scaling`` (granite's 8)."""
    if cfg.logits_scaling == 1.0:
        return logits
    return logits / jnp.asarray(cfg.logits_scaling, logits.dtype)


def token_loss(logits: jnp.ndarray, batch: Dict, cfg: ArchConfig) -> jnp.ndarray:
    """Mean cross-entropy against batch['labels'] (mask label < 0)."""
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    if cfg.modality == "vision_stub" and lf.shape[1] != labels.shape[1]:
        lf = lf[:, lf.shape[1] - labels.shape[1]:, :]  # text positions only
    logz = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _vocab_logits_spec(vocab_size: int, batch: int, batch_axes):
    """P(batch_axes, None, "model") under the ambient mesh — chunk logits are
    sharded on batch (data axes) AND vocab (model axis)."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        names = list(mesh.axis_names)
        shape = dict(mesh.shape)
    except Exception:  # noqa: BLE001
        return None
    if not names:
        return None
    from jax.sharding import PartitionSpec as P

    v = "model" if "model" in names and vocab_size % shape["model"] == 0 else None
    baxes = tuple(a for a in batch_axes if a in names)
    if baxes and batch % int(np.prod([shape[a] for a in baxes])) == 0:
        b = baxes if len(baxes) > 1 else baxes[0]
    else:
        b = None
    if b is None and v is None:
        return None
    return P(b, None, v)


def chunked_ce_loss(h: jnp.ndarray, head_w: jnp.ndarray, batch: Dict,
                    cfg: ArchConfig, *, chunk: int = 512) -> jnp.ndarray:
    """Cross-entropy WITHOUT materializing [B, S, V] logits.

    Scans over sequence chunks; each chunk's logits are sharded
    (batch, -, vocab->model) and rematerialized in the backward pass
    (jax.checkpoint), so peak memory is [B, chunk, V/model_shards] instead of
    the full fp32 logits tensor. head_w: [d, V].
    """
    labels = batch["labels"]
    if h.shape[1] != labels.shape[1]:  # vlm: loss over text positions only
        h = h[:, h.shape[1] - labels.shape[1]:, :]
    B, S, d = h.shape
    c = min(chunk, S)
    while S % c:
        c -= 1
    n = S // c
    hs = h.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    ys = labels.reshape(B, n, c).transpose(1, 0, 2)
    spec = _vocab_logits_spec(head_w.shape[-1], B, cfg.batch_axes)

    @jax.checkpoint
    def chunk_loss(h_c, y_c):
        logits = scale_logits((h_c @ head_w.astype(h_c.dtype))
                              .astype(jnp.float32), cfg)
        if spec is not None:
            logits = jax.lax.with_sharding_constraint(logits, spec)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(y_c, 0)[..., None], axis=-1)[..., 0]
        m = (y_c >= 0).astype(jnp.float32)
        return jnp.sum((logz - gold) * m), jnp.sum(m)

    def body(carry, xs):
        tot, cnt = carry
        l, m = chunk_loss(*xs)
        return (tot + l, cnt + m), None

    (total, count), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                                     (hs, ys))
    return total / jnp.maximum(count, 1.0)


def build(cfg: ArchConfig) -> LM:
    return LM(cfg)
