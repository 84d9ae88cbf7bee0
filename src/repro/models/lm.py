"""The shared language-model shell for every assigned architecture.

A model is: embedding -> N plannable blocks -> final norm -> lm head.
Families differ only in what a block contains (attention+MLP, MoE, SSD
mixer, hybrid, encoder/decoder).  The Mimose planner sees the model as an
ordered list of *plan units* (= blocks in ``unrolled`` mode, layer-chunks
in ``scan`` mode) and decides which units to rematerialise.

Public surface:
    lm = LM(cfg, attn_impl="xla")
    params = lm.init(key)
    logits, aux = lm.forward(params, batch, remat_mask)
    loss, metrics = lm.loss(params, batch, remat_mask)
    cache = lm.init_cache(batch_size, max_len, dtype)
    logits, cache = lm.decode_step(params, tokens, cache, index)
    units = lm.plan_units(params, batch)   # for the Mimose collector
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.actions import Action, as_actions
from repro.config import ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MOE
from repro.models import hymba as HY

Array = jax.Array

# the checkpoint_name tag the OFFLOAD action pins to host memory: the
# unit's residual-stream input (its recompute checkpoint).  Applying
# OFFLOAD moves this named tensor to pinned_host instead of keeping it
# in HBM — the jax-realisable form of activation offload (the planner's
# cost model prices the residual traffic; see docs/ARCHITECTURE.md
# "Hybrid remat+offload plans").
OFFLOAD_RESIDUAL_NAME = "mimose_offload_resid"


def host_offload_policy():
    """``jax.checkpoint`` policy offloading the named residual-stream
    checkpoint to pinned host memory."""
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=[],
        names_which_can_be_offloaded=[OFFLOAD_RESIDUAL_NAME],
        offload_src="device", offload_dst="pinned_host")


def _offload_unit(fn):
    """Wrap a pure ``fn(params, x, ...)`` unit so its input checkpoint is
    tagged for host offload, then checkpoint it under the offload
    policy.  The checkpoint is jit-wrapped because the host transfer
    (``TransferToMemoryKind``) is only legal under jit; under an outer
    jit (the trainer's step) the nested jit is inlined."""
    def tagged(p, x, *rest):
        return fn(p, checkpoint_name(x, OFFLOAD_RESIDUAL_NAME), *rest)
    return jax.jit(jax.checkpoint(tagged, policy=host_offload_policy()))


# ---------------------------------------------------------------------------
# SPMD offload capability probe
#
# Older launch paths degraded EVERY multi-device mesh to offload_exec =
# False because some XLA builds cannot shard the host-offload
# custom-calls.  That threw the offload axis away on runtimes that CAN
# shard them.  The probe below compiles a minimal offloaded grad under
# the actual mesh once (cached per mesh signature) and only falls back
# where the compile genuinely fails — with a single warning per mesh so
# the degradation is never silent (the planner keeps emitting typed
# OFFLOAD actions either way; execution just prices them as remat).
# ---------------------------------------------------------------------------

_spmd_offload_cache: Dict[tuple, bool] = {}
_spmd_offload_warned: set = set()


def _mesh_probe_sig(mesh) -> tuple:
    d = mesh.devices
    return (tuple(mesh.axis_names), tuple(int(s) for s in d.shape),
            str(getattr(d.flat[0], "platform", "cpu")))


def spmd_offload_supported(mesh=None) -> bool:
    """True when OFFLOAD actions can execute as real host offload under
    ``mesh``.  Single device (or no mesh): always.  SPMD: try-compiling
    a tiny offloaded grad under the mesh answers for this exact
    (jaxlib, backend, mesh-shape) combination."""
    if mesh is None or int(mesh.devices.size) <= 1:
        return True
    sig = _mesh_probe_sig(mesh)
    hit = _spmd_offload_cache.get(sig)
    if hit is not None:
        return hit
    try:
        from jax.sharding import NamedSharding, PartitionSpec

        def unit(y):
            y = checkpoint_name(y, OFFLOAD_RESIDUAL_NAME)
            return (jnp.sin(y) * y).sum()

        ckpt = jax.checkpoint(unit, policy=host_offload_policy())
        sh = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
        x = jnp.zeros((int(mesh.devices.size), 8), jnp.float32)
        jax.jit(jax.grad(ckpt), in_shardings=sh,
                out_shardings=sh).lower(x).compile()
        ok = True
    except Exception:
        ok = False
    _spmd_offload_cache[sig] = ok
    return ok


def configure_offload(lm: "LM", mesh=None) -> bool:
    """Set ``lm.offload_exec`` from the probe.  Returns True when the
    mesh lost real offload execution (OFFLOAD will degrade to remat) —
    callers count that as an offload fallback; the warning fires once
    per mesh signature."""
    ok = spmd_offload_supported(mesh)
    lm.offload_exec = ok
    if not ok:
        sig = (_mesh_probe_sig(mesh) if mesh is not None
               else ("<no-mesh>",))
        if sig not in _spmd_offload_warned:
            _spmd_offload_warned.add(sig)
            import warnings
            warnings.warn(
                f"host offload unavailable under mesh {sig}: OFFLOAD "
                f"actions will execute as plain remat (plans keep their "
                f"typed actions; step time loses the offload axis)",
                RuntimeWarning, stacklevel=2)
    return not ok


# ---------------------------------------------------------------------------
# per-family block init / apply
# ---------------------------------------------------------------------------

def _block_kind(cfg: ModelConfig, decoder: bool = True) -> str:
    if not decoder:
        return "enc"
    if cfg.family == "moe":
        return "moe"
    if cfg.family == "ssm":
        return "ssm"
    if cfg.family == "hybrid":
        return "hybrid"
    if cfg.family == "encdec":
        return "dec"
    return "dense"


def block_init(key: Array, cfg: ModelConfig, kind: str, dtype) -> dict:
    d = cfg.d_model
    keys = jax.random.split(key, 6)
    p: dict = {"norm1": L.rmsnorm_init(d, dtype)}
    if kind == "ssm":
        p["ssm"] = M.mamba2_init(keys[0], cfg, dtype)
        if cfg.d_ff:
            p["norm2"] = L.rmsnorm_init(d, dtype)
            p["mlp"] = L.mlp_init(keys[1], d, cfg.d_ff, cfg.mlp_act, dtype)
        return p
    if kind == "hybrid":
        p["mixer"] = HY.hymba_init(keys[0], cfg, dtype)
        p["norm2"] = L.rmsnorm_init(d, dtype)
        p["mlp"] = L.mlp_init(keys[1], d, cfg.d_ff, cfg.mlp_act, dtype)
        return p
    # attention-bearing kinds
    p["attn"] = L.attention_init(keys[0], cfg, dtype)
    if kind == "dec":
        p["norm_cross"] = L.rmsnorm_init(d, dtype)
        p["cross"] = L.attention_init(keys[2], cfg, dtype)
    p["norm2"] = L.rmsnorm_init(d, dtype)
    if kind == "moe":
        p["moe"] = MOE.moe_init(keys[1], cfg, dtype)
    else:
        p["mlp"] = L.mlp_init(keys[1], d, cfg.d_ff, cfg.mlp_act, dtype)
    return p


def block_apply(params: dict, cfg: ModelConfig, x: Array, kind: str, *,
                positions: Array,
                layer_is_global=True,
                cache: Optional[dict] = None,
                cache_index: Optional[Array] = None,
                decode: bool = False,
                enc_out: Optional[Array] = None,
                mrope_positions: Optional[Array] = None,
                impl: str = "xla",
                seq_lens: Optional[Array] = None,
                ) -> Tuple[Array, Optional[dict], Array]:
    """Returns (x, new_cache, aux_loss).

    ``seq_lens``: optional (B,) true sequence lengths of a bucket-padded
    batch — threaded into the attention key masks and the SSD state
    masks so padded positions do no work and leak nothing.
    """
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Array] = {}
    eps = cfg.norm_eps

    if kind == "ssm":
        h, (new_ssm, new_conv) = M.mamba2_apply(
            params["ssm"], cfg, L.rmsnorm_apply(params["norm1"], x, eps),
            ssm_state=None if cache is None else cache["ssm"],
            conv_state=None if cache is None else cache["conv"],
            decode=decode, seq_lens=seq_lens)
        x = x + h
        if cache is not None:
            new_cache.update(ssm=new_ssm, conv=new_conv)
        if cfg.d_ff:
            x = x + L.mlp_apply(params["mlp"],
                                L.rmsnorm_apply(params["norm2"], x, eps),
                                cfg.mlp_act)
        return x, (new_cache or None), aux

    if kind == "hybrid":
        h, new_kv, (new_ssm, new_conv) = HY.hymba_apply(
            params["mixer"], cfg, L.rmsnorm_apply(params["norm1"], x, eps),
            positions=positions, layer_is_global=layer_is_global,
            kv_cache=None if cache is None else {"k": cache["k"], "v": cache["v"]},
            cache_index=cache_index,
            ssm_state=None if cache is None else cache["ssm"],
            conv_state=None if cache is None else cache["conv"],
            decode=decode, impl=impl, seq_lens=seq_lens)
        x = x + h
        if cache is not None:
            new_cache.update(k=new_kv["k"], v=new_kv["v"], ssm=new_ssm, conv=new_conv)
        x = x + L.mlp_apply(params["mlp"],
                            L.rmsnorm_apply(params["norm2"], x, eps), cfg.mlp_act)
        return x, (new_cache or None), aux

    # attention-bearing blocks -------------------------------------------
    with jax.named_scope("attn"):
        h, new_kv = L.attention_apply(
            params["attn"], cfg, L.rmsnorm_apply(params["norm1"], x, eps),
            positions=positions, layer_is_global=layer_is_global,
            kv_cache=None if cache is None
            else {"k": cache["k"], "v": cache["v"]},
            cache_index=cache_index, impl=impl,
            mrope_positions=mrope_positions,
            causal=(kind != "enc"), kv_len=seq_lens)
    x = x + h
    if new_kv is not None:
        new_cache.update(k=new_kv["k"], v=new_kv["v"])

    if kind == "dec":
        # cross attention over encoder output (k/v projected here, or cached)
        hx = L.rmsnorm_apply(params["norm_cross"], x, eps)
        if cache is not None and "ck" in cache:
            ck, cv = cache["ck"], cache["cv"]
            new_cache.update(ck=ck, cv=cv)
        else:
            B, F = enc_out.shape[0], enc_out.shape[1]
            hd = cfg.resolved_head_dim()
            ck = (enc_out @ params["cross"]["wk"]).reshape(B, F, cfg.num_kv_heads, hd)
            cv = (enc_out @ params["cross"]["wv"]).reshape(B, F, cfg.num_kv_heads, hd)
            if cache is not None:
                new_cache.update(ck=ck, cv=cv)
        hc, _ = L.attention_apply(params["cross"], cfg, hx,
                                  positions=positions, cross_kv=(ck, cv))
        x = x + hc

    h2 = L.rmsnorm_apply(params["norm2"], x, eps)
    if kind == "moe":
        mo, aux = MOE.moe_apply(params["moe"], cfg, h2)
        x = x + mo
    else:
        with jax.named_scope("mlp"):
            x = x + L.mlp_apply(params["mlp"], h2, cfg.mlp_act)
    return x, (new_cache or None), aux


# ---------------------------------------------------------------------------
# plan units
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PlanUnit:
    """One schedulable unit: a block (unrolled) or a layer chunk (scan)."""
    name: str
    index: int                     # forward timestamp order
    params: Any
    apply: Callable[[Any, Array], Array]   # pure fn(params, x) -> x
    flops: float = 0.0             # analytic forward flops (filled by collector)
    # behavioural statics baked into ``apply`` (block kind, local/global
    # attention flag, chunk width...).  Two units with equal signature AND
    # equal param/input shapes trace to identical residual footprints, so
    # the collector measures only one of them (O(#unique units) traces).
    # None disables deduplication for this unit.
    signature: Optional[tuple] = None


# ---------------------------------------------------------------------------
# blocked output head and cross-entropy
# ---------------------------------------------------------------------------

# a block holds at least this many tokens: a block reads the head twice
# and reads and writes its fp32 gradient once, 12-16 bytes per entry
# against 6 FLOPs per entry and token; at 1024 tokens one v5e chip's
# matrix unit takes about twice as long as that traffic, which then
# hides under it (at 512 tokens a v5e ran the fp32 gradient sum at half
# its peak)
HEAD_MIN_TOKENS = 1024
# beyond that, as many tokens as keep one block's fp32 logits this small
HEAD_BLOCK_BYTES = 1 << 28


def head_block_tokens(n_tokens: int, vocab: int) -> int:
    """Tokens in one block of the blocked output head and loss.

    The rule: a block may hold as many tokens as keep its fp32 logits
    within ``HEAD_BLOCK_BYTES`` (256 MiB), rounded down to a multiple of
    128 (the matrix unit's width), but at least ``HEAD_MIN_TOKENS``
    (1024), below which it waits on the head's weight traffic.  The
    step's tokens are cut into the fewest such blocks, of equal size
    rounded up to 8, so little of the last block is padding.
    Vocabulary 151936 takes blocks of 1024 tokens; 30522 at most 2176
    (2048 for 48 x 512 tokens)."""
    n = int(n_tokens)
    most = max(HEAD_MIN_TOKENS,
               HEAD_BLOCK_BYTES // (4 * int(vocab)) // 128 * 128)
    per_block = -(-n // -(-n // most))
    return -(-per_block // 8) * 8


def _xent_block(h, w, labels, scale, vocab_rows: bool) -> dict:
    """One block of tokens through the output head: the scaled negative
    log-likelihood sum and its gradients, with the block's logits and
    their cotangent.  ``h`` (T, d); ``w`` (V, d) when ``vocab_rows``,
    else (d, V); ``scale`` (T,) each token's weight over the total.

    dlogits = (softmax - onehot) * scale, cast to the operands' dtype as
    autodiff of ``h @ w`` would; both gradient products accumulate in
    fp32.  The label logit is a one-hot contraction inside the block, so
    a model-sharded vocabulary reduces instead of all-gathering."""
    f32 = jnp.float32
    dims = (((1,), (1,)), ((), ())) if vocab_rows else (((1,), (0,)), ((), ()))
    logits = jax.lax.dot_general(h, w, dims, preferred_element_type=f32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=f32)
    nll = lse - jnp.sum(logits * onehot, axis=-1)
    dlogits = ((jnp.exp(logits - lse[:, None]) - onehot)
               * scale[:, None]).astype(jnp.result_type(h, w))
    tokens = (((0,), (0,)), ((), ()))
    if vocab_rows:
        dw = jax.lax.dot_general(dlogits, h, tokens,
                                 preferred_element_type=f32)
        dh = jnp.dot(dlogits, w, preferred_element_type=f32)
    else:
        dw = jax.lax.dot_general(h, dlogits, tokens,
                                 preferred_element_type=f32)
        dh = jax.lax.dot_general(dlogits, w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)
    return {"loss": jnp.sum(nll * scale), "dh": dh.astype(h.dtype),
            "dw": dw, "logits": logits, "dlogits": dlogits}


def _xent_grads(h, w, labels, scale, block: int, vocab_rows: bool):
    """``blocked_xent``'s value and its gradients in ``h`` and ``w``,
    one ``lax.scan`` step a block; tokens past the last whole block are
    padded with weight 0."""
    n, d = h.shape
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
        scale = jnp.pad(scale, (0, pad))

    def body(carry, xs):
        (loss, dw), (hb, lb, sb) = carry, xs
        out = _xent_block(hb, w, lb, sb, vocab_rows)
        return (loss + out["loss"], dw + out["dw"]), out["dh"]

    init = (jnp.zeros((), jnp.float32), jnp.zeros(w.shape, jnp.float32))
    (loss, dw), dh = jax.lax.scan(
        body, init, (h.reshape(nb, block, d), labels.reshape(nb, block),
                     scale.reshape(nb, block)))
    return loss, dh.reshape(nb * block, d)[:n], dw.astype(w.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def blocked_xent(h, w, labels, scale, block: int, vocab_rows: bool):
    """sum_t scale_t * (logsumexp(h_t w) - (h_t w)[labels_t]) over blocks
    of ``block`` tokens.  The forward pass computes the gradients in
    ``h`` and ``w`` block by block, so the head costs the forward and
    backward FLOPs of ``h @ w`` with no recompute, and holds one block's
    logits; the backward only scales them by the incoming cotangent."""
    return _xent_grads(h, w, labels, scale, block, vocab_rows)[0]


def _blocked_xent_fwd(h, w, labels, scale, block, vocab_rows):
    loss, dh, dw = _xent_grads(h, w, labels, scale, block, vocab_rows)
    return loss, (dh, dw)


def _blocked_xent_bwd(block, vocab_rows, res, g):
    dh, dw = res
    return ((g * dh).astype(dh.dtype), (g * dw).astype(dw.dtype), None,
            None)


blocked_xent.defvjp(_blocked_xent_fwd, _blocked_xent_bwd)


# ---------------------------------------------------------------------------
# the LM shell
# ---------------------------------------------------------------------------

class LM:
    def __init__(self, cfg: ModelConfig, attn_impl: str = "xla"):
        self.cfg = cfg
        self.attn_impl = attn_impl
        self.kind = _block_kind(cfg)
        self.dtype = jnp.dtype(cfg.dtype)
        # perf knobs (set by the launcher; see EXPERIMENTS.md §Perf):
        # Megatron-style sequence-parallel residual stream — shard the
        # seq axis of the inter-block activations over the model axis.
        self.act_sharding = None          # NamedSharding or None
        # forward()'s logits in f32 (False: the model's dtype); the
        # training loss computes each block's logits in f32 either way
        self.logits_f32 = True
        # prefill: emit logits for the last position only (serving needs
        # nothing else; full-sequence logits dominate prefill memory)
        self.last_logits_only = False
        # execute OFFLOAD actions as real host offload (jax.checkpoint
        # offload policy).  False degrades OFFLOAD to plain remat at
        # execution time while keeping the typed plan — needed under
        # SPMD lowering, where current XLA cannot shard the host-offload
        # custom-calls (launch/steps.py flips this for >1-device meshes)
        self.offload_exec = True

    def _constrain(self, x: Array) -> Array:
        if self.act_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, self.act_sharding)
        return x

    # -- init -------------------------------------------------------------
    def init(self, key: Array) -> dict:
        cfg, dt = self.cfg, self.dtype
        keys = jax.random.split(key, cfg.num_layers + cfg.encoder_layers + 3)
        params: dict = {
            "embed": L.embed_init(keys[-1], cfg.vocab_size, cfg.d_model, dt),
            "final_norm": L.rmsnorm_init(cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(keys[-2], cfg.d_model,
                                             cfg.vocab_size, dt)
        blocks = [block_init(keys[i], cfg, self.kind, dt)
                  for i in range(cfg.num_layers)]
        if cfg.remat_mode == "scan":
            params["blocks"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *blocks)
        else:
            params["blocks"] = blocks
        if cfg.encoder_layers:
            enc = [block_init(keys[cfg.num_layers + i], cfg, "enc", dt)
                   for i in range(cfg.encoder_layers)]
            params["encoder"] = {
                "blocks": enc,
                "final_norm": L.rmsnorm_init(cfg.d_model, dt),
            }
        return params

    # -- per-layer local/global flags (gemma3 pattern) ----------------------
    def _is_global(self, i: int) -> bool:
        g = self.cfg.global_interval
        if not self.cfg.sliding_window:
            return True
        if not g:
            return False              # uniform sliding window
        return (i + 1) % g == 0

    def _global_flags(self) -> Array:
        return jnp.array([self._is_global(i) for i in range(self.cfg.num_layers)])

    # -- embedding / positions -------------------------------------------
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, St = tokens.shape
        x = params["embed"][tokens]
        mrope_positions = None
        if cfg.family == "vlm" and cfg.vision_tokens:
            ve = batch["vision_embeds"].astype(x.dtype)      # (B, vt, d)
            x = jnp.concatenate([ve, x], axis=1)
            vt = cfg.vision_tokens
            side = max(int(math.sqrt(vt)), 1)
            S = vt + St
            if cfg.mrope:
                idx = jnp.arange(vt)
                tpos = jnp.zeros((vt,), jnp.int32)
                hpos = (idx // side).astype(jnp.int32)
                wpos = (idx % side).astype(jnp.int32)
                text = jnp.arange(St, dtype=jnp.int32) + side
                three = jnp.stack([
                    jnp.concatenate([tpos, text]),
                    jnp.concatenate([hpos, text]),
                    jnp.concatenate([wpos, text]),
                ])                                            # (3, S)
                mrope_positions = jnp.broadcast_to(three[:, None, :], (3, B, S))
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        else:
            positions = batch.get("positions")
            if positions is None:
                positions = jnp.broadcast_to(
                    jnp.arange(St, dtype=jnp.int32), (B, St))
        return x, positions, mrope_positions

    def _encode(self, params, batch, remat_enc=None):
        """Run the (bidirectional) encoder over stub frame embeddings."""
        cfg = self.cfg
        frames = batch["frames"].astype(self.dtype)          # (B, F, d)
        B, F, _ = frames.shape
        pos = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), (B, F))
        x = frames
        enc_actions = (as_actions(remat_enc) if remat_enc is not None
                       else None)
        for i, bp in enumerate(params["encoder"]["blocks"]):
            def one(p, xx):
                y, _, _ = block_apply(p, cfg, xx, "enc", positions=pos,
                                      impl=self.attn_impl)
                return y
            if enc_actions is not None:
                if enc_actions[i] is Action.REMAT:
                    one = jax.checkpoint(one)
                elif enc_actions[i] is Action.OFFLOAD:
                    one = (_offload_unit(one) if self.offload_exec
                           else jax.checkpoint(one))
            x = one(bp, x)
        return L.rmsnorm_apply(params["encoder"]["final_norm"], x, cfg.norm_eps)

    # -- forward -----------------------------------------------------------
    def forward(self, params, batch, remat_mask=None,
                remat_policy=None) -> Tuple[Array, Array]:
        """remat_mask: per-unit plan over plan units (blocks or chunks) —
        either the legacy bool sequence (True = rematerialise) or a
        typed ``repro.actions.Action`` sequence; ``OFFLOAD`` units pin
        their residual-stream checkpoint to host memory via the
        ``host_offload_policy`` instead of keeping it in HBM.

        When the batch carries ``lengths`` ((B,) true sequence lengths of
        a bucket-padded batch), they are threaded into every block so the
        kernels mask — and, where blockwise, skip — the padded tail.
        """
        cfg = self.cfg
        x, aux = self._trunk(params, batch, remat_mask, remat_policy)
        if self.last_logits_only:
            x = x[:, -1:]
        with jax.named_scope("head"):
            x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
            w, vocab_rows = self._head_weight(params)
            logits = x @ (w.T if vocab_rows else w)
            if self.logits_f32:
                logits = logits.astype(jnp.float32)
        return logits, aux

    def _head_weight(self, params) -> Tuple[Array, bool]:
        """The output head as stored, and whether its rows are the
        vocabulary: the tied embedding (V, d), or ``lm_head`` (d, V)."""
        if self.cfg.tie_embeddings:
            return params["embed"], True
        return params["lm_head"], False

    def _trunk(self, params, batch, remat_mask=None, remat_policy=None):
        """Embedding and every plan unit: the last hidden state (before
        the final norm) and the summed auxiliary loss."""
        cfg = self.cfg
        x, positions, mrope_positions = self._embed_inputs(params, batch)
        aux = jnp.zeros((), jnp.float32)
        seq_lens = batch.get("lengths")
        if seq_lens is not None:
            seq_lens = jnp.asarray(seq_lens, jnp.int32)
            if cfg.family == "vlm" and cfg.vision_tokens:
                # vision patches are prepended and always real tokens
                seq_lens = seq_lens + cfg.vision_tokens

        n_units = self.num_plan_units()
        actions = (as_actions(remat_mask) if remat_mask is not None
                   else (Action.KEEP,) * n_units)
        assert len(actions) == n_units, (len(actions), n_units)

        enc_out = None
        enc_units = self._num_enc_units()
        if cfg.encoder_layers:
            enc_out = self._encode(params, batch,
                                   remat_enc=actions[:enc_units])
        dec_actions = actions[enc_units:]

        if cfg.remat_mode == "scan":
            x, aux = self._forward_scan(params, x, positions, dec_actions,
                                        enc_out, mrope_positions,
                                        remat_policy, seq_lens)
        else:
            for i, bp in enumerate(params["blocks"]):
                def one(p, xx):
                    y, _, a = block_apply(
                        p, cfg, xx, self.kind, positions=positions,
                        layer_is_global=self._is_global(i),
                        enc_out=enc_out, mrope_positions=mrope_positions,
                        impl=self.attn_impl, seq_lens=seq_lens)
                    return y, a
                if dec_actions[i] is Action.REMAT:
                    one = jax.checkpoint(one, policy=remat_policy)
                elif dec_actions[i] is Action.OFFLOAD:
                    one = (_offload_unit(one) if self.offload_exec
                           else jax.checkpoint(one, policy=remat_policy))
                # the scope names the unit's ops in a profile: forward,
                # backward (transpose) and recompute (rematted_computation)
                with jax.named_scope(f"unit{i}"):
                    x, a = one(bp, x)
                x = self._constrain(x)
                aux = aux + a
        return x, aux

    def _forward_scan(self, params, x, positions, chunk_actions, enc_out,
                      mrope_positions, remat_policy, seq_lens=None):
        cfg = self.cfg
        bounds = self._chunk_bounds()
        aux = jnp.zeros((), jnp.float32)
        chunk_actions = as_actions(chunk_actions)

        def make_body(flag):
            # ``flag`` is a STATIC python bool (chunks are type-homogeneous)
            # so local chunks take the banded sliding-window path.
            def body(carry, p_i):
                xx, ax = carry
                y, _, a = block_apply(p_i, cfg, xx, self.kind,
                                      positions=positions,
                                      layer_is_global=flag,
                                      enc_out=enc_out,
                                      mrope_positions=mrope_positions,
                                      impl=self.attn_impl,
                                      seq_lens=seq_lens)
                y = self._constrain(y)
                return (y, ax + a), None
            return body

        for c, (s, e) in enumerate(bounds):
            p_chunk = jax.tree_util.tree_map(lambda a: a[s:e], params["blocks"])
            body = make_body(self._chunk_flag(s, e))
            if chunk_actions[c] is Action.REMAT:
                bfn = jax.checkpoint(body, policy=remat_policy)
            elif chunk_actions[c] is Action.OFFLOAD:
                if self.offload_exec:
                    def off_body(carry, p_i, _b=body):
                        xx, ax = carry
                        return _b((checkpoint_name(xx,
                                                   OFFLOAD_RESIDUAL_NAME),
                                   ax), p_i)
                    bfn = jax.checkpoint(off_body,
                                         policy=host_offload_policy())
                else:
                    bfn = jax.checkpoint(body, policy=remat_policy)
            else:
                bfn = body
            (x, aux), _ = jax.lax.scan(bfn, (x, aux), p_chunk)
        return x, aux

    def _chunk_bounds(self) -> List[Tuple[int, int]]:
        L_ = self.cfg.num_layers
        if self.cfg.sliding_window and self.cfg.global_interval:
            # type-homogeneous chunks (runs of local layers + global
            # singletons) so the local/global flag is STATIC per chunk and
            # local chunks can take the banded-attention path.
            bounds, s = [], 0
            for i in range(L_):
                if self._is_global(i):
                    if i > s:
                        bounds.append((s, i))
                    bounds.append((i, i + 1))
                    s = i + 1
            if s < L_:
                bounds.append((s, L_))
            return bounds
        K = max(1, min(self.cfg.scan_chunks, L_))
        step = math.ceil(L_ / K)
        return [(s, min(s + step, L_)) for s in range(0, L_, step)]

    def _chunk_flag(self, s: int, e: int) -> bool:
        """Static local/global flag for a type-homogeneous chunk."""
        flags = {self._is_global(i) for i in range(s, e)}
        if len(flags) == 1:
            return flags.pop()
        return True        # mixed chunk (no banding): treat as global/full

    def _num_enc_units(self) -> int:
        return self.cfg.encoder_layers

    # -- static per-unit facts for the analytic cost model -------------------
    def plan_unit_meta(self, batch) -> List[Dict[str, Any]]:
        """One dict per plan unit, timestamp order: the static facts the
        ``launch/roofline.py`` cost model needs to price a unit's forward
        (= its recompute cost) at this batch's geometry.  Works on arrays
        and ``ShapeDtypeStruct`` batches alike — no tracing, so the
        planner can call it per bucket for free."""
        cfg = self.cfg
        B, St = batch["tokens"].shape
        S = St + (cfg.vision_tokens
                  if cfg.family == "vlm" and cfg.vision_tokens else 0)
        F = batch["frames"].shape[1] if "frames" in batch else 0
        metas: List[Dict[str, Any]] = []
        for i in range(cfg.encoder_layers):
            metas.append({"kind": "enc", "layers": 1, "batch": B, "seq": F,
                          "is_global": True})
        if cfg.remat_mode == "scan":
            for s, e in self._chunk_bounds():
                metas.append({"kind": self.kind, "layers": e - s, "batch": B,
                              "seq": S, "is_global": self._chunk_flag(s, e),
                              "enc_frames": F})
        else:
            for i in range(cfg.num_layers):
                metas.append({"kind": self.kind, "layers": 1, "batch": B,
                              "seq": S, "is_global": self._is_global(i),
                              "enc_frames": F})
        return metas

    def num_plan_units(self) -> int:
        if self.cfg.remat_mode == "scan":
            return self._num_enc_units() + len(self._chunk_bounds())
        return self._num_enc_units() + self.cfg.num_layers

    # -- loss ---------------------------------------------------------------
    def loss(self, params, batch, remat_mask=None, remat_policy=None):
        """Token-weighted mean cross-entropy (plus the family's auxiliary
        loss).  The final norm, output head and cross-entropy run over
        blocks of tokens (``blocked_xent``), whose gradient is taken in
        the forward pass: no (B, S, V) logits exist."""
        cfg = self.cfg
        x, aux = self._trunk(params, batch, remat_mask, remat_policy)
        labels = batch["labels"]
        if cfg.family == "vlm" and cfg.vision_tokens:
            x = x[:, cfg.vision_tokens:]                     # text positions only
        weights = batch.get("weights")
        if weights is None:
            weights = jnp.ones(labels.shape, jnp.float32)
        total_w = jnp.maximum(jnp.sum(weights), 1.0)
        B, S, d = x.shape
        w, vocab_rows = self._head_weight(params)
        with jax.named_scope("head"):
            h = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
            ce = blocked_xent(h.reshape(B * S, d), w, labels.reshape(-1),
                              (weights / total_w).astype(jnp.float32)
                              .reshape(-1),
                              head_block_tokens(B * S, cfg.vocab_size),
                              vocab_rows)
        loss = ce + aux
        return loss, {"ce": ce, "aux": aux, "tokens": total_w}

    def head_blocks(self, batch) -> int:
        """Blocks of tokens the output head and loss run in."""
        n = math.prod(batch["tokens"].shape)
        return -(-n // head_block_tokens(n, self.cfg.vocab_size))

    def head_parts(self, batch) -> dict:
        """What the blocked head and loss hold at once, as shapes: the
        final hidden state (the final norm's input, kept for its
        backward) and its normed copy, the hidden state's whole
        gradient, the fp32 gradient of the head being summed, and one
        block's forward and gradient (``_xent_block`` under
        ``jax.eval_shape``).  Works on arrays and ``ShapeDtypeStruct``
        batches alike."""
        cfg = self.cfg
        B, St = batch["tokens"].shape
        n, d, V = B * St, cfg.d_model, cfg.vocab_size
        block = head_block_tokens(n, V)
        vocab_rows = cfg.tie_embeddings
        hidden = jax.ShapeDtypeStruct((n, d), self.dtype)
        w = jax.ShapeDtypeStruct((V, d) if vocab_rows else (d, V),
                                 self.dtype)
        one = jax.eval_shape(
            lambda hb, ww, lb, sb: _xent_block(hb, ww, lb, sb, vocab_rows),
            jax.ShapeDtypeStruct((block, d), self.dtype), w,
            jax.ShapeDtypeStruct((block,), jnp.int32),
            jax.ShapeDtypeStruct((block,), jnp.float32))
        padded = self.head_blocks(batch) * block
        return {"final_hidden": hidden, "normed": hidden,
                "hidden_grad": jax.ShapeDtypeStruct((padded, d), self.dtype),
                "head_grad_sum": one["dw"], "block": one}

    # -- decode -------------------------------------------------------------
    def init_cache(self, batch_size: int, max_len: int) -> Any:
        cfg, dt = self.cfg, self.dtype
        hd = cfg.resolved_head_dim()
        d_inner, H, N, conv_dim = (M.mamba2_dims(cfg) if cfg.ssm_state
                                   else (0, 0, 0, 0))

        def one_cache():
            c: dict = {}
            if self.kind in ("dense", "moe", "dec", "hybrid"):
                c["k"] = jnp.zeros((batch_size, max_len, cfg.num_kv_heads, hd), dt)
                c["v"] = jnp.zeros((batch_size, max_len, cfg.num_kv_heads, hd), dt)
            if self.kind in ("ssm", "hybrid"):
                c["ssm"] = jnp.zeros((batch_size, H, cfg.ssm_head_dim, N),
                                     jnp.float32)
                c["conv"] = jnp.zeros((batch_size, cfg.conv_kernel - 1, conv_dim), dt)
            if self.kind == "dec":
                F = cfg.encoder_frames or max_len
                c["ck"] = jnp.zeros((batch_size, F, cfg.num_kv_heads, hd), dt)
                c["cv"] = jnp.zeros((batch_size, F, cfg.num_kv_heads, hd), dt)
            return c

        caches = [one_cache() for _ in range(cfg.num_layers)]
        if cfg.remat_mode == "scan":
            return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *caches)
        return caches

    # -- batched cache slots (continuous-batching serve engine) -------------
    # A pool cache is just ``init_cache(slots, max_len)``: batch rows are
    # request slots.  The three operations below move whole rows between
    # a staging cache (one prefilling request) and a pool at STATIC
    # shapes — ``slot`` is a traced scalar, so the engine compiles one
    # executable per (bucket, slots) geometry, never per slot index.

    def cache_batch_axis(self) -> int:
        """Axis of the request/batch dimension in every cache leaf
        (scan mode stacks a leading layer axis)."""
        return 1 if self.cfg.remat_mode == "scan" else 0

    def cache_insert(self, pool: Any, rows: Any, slot) -> Any:
        """Write ``rows`` (a cache whose batch dim holds >= 1 request
        rows, e.g. a prefill staging cache) into ``pool`` starting at
        batch row ``slot``.  Shapes must match outside the batch axis."""
        ax = self.cache_batch_axis()
        return jax.tree_util.tree_map(
            lambda p, r: jax.lax.dynamic_update_slice_in_dim(
                p, r.astype(p.dtype), slot, axis=ax), pool, rows)

    def cache_extract(self, pool: Any, slot) -> Any:
        """Read one request row out of ``pool`` as a batch-1 cache."""
        ax = self.cache_batch_axis()
        return jax.tree_util.tree_map(
            lambda p: jax.lax.dynamic_slice_in_dim(p, slot, 1, axis=ax),
            pool)

    def cache_evict(self, pool: Any, slot) -> Any:
        """Zero one request row of ``pool`` (slot freed: no stale state
        survives into the next tenant — insert overwrites the row anyway,
        this keeps freed slots inert and debuggable)."""
        ax = self.cache_batch_axis()
        return jax.tree_util.tree_map(
            lambda p: jax.lax.dynamic_update_slice_in_dim(
                p, jnp.zeros_like(
                    jax.lax.dynamic_slice_in_dim(p, slot, 1, axis=ax)),
                slot, axis=ax), pool)

    def decode_step(self, params, tokens, cache, index):
        """tokens: (B, C) int32 — C == 1 for token-by-token decode, a
        whole block for chunked prefill (``train.serve``); index: scalar
        position of the first token, or a (B,) int32 vector of per-row
        positions — the continuous-batching engine's form, where every
        batch row is a different request at its own decode position
        (rows parked at index == cache length write nothing).  Returns
        (logits (B,C,V), new_cache) — the cache advances by C positions."""
        cfg = self.cfg
        B, C = tokens.shape
        x = params["embed"][tokens]
        idx = jnp.asarray(index, jnp.int32)
        if idx.ndim >= 1:
            positions = idx[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
            index = idx
        else:
            positions = index + jnp.broadcast_to(
                jnp.arange(C, dtype=jnp.int32), (B, C))
        mrope_positions = None
        if cfg.mrope:
            mrope_positions = jnp.broadcast_to(positions[None], (3, B, C))

        if cfg.remat_mode == "scan":
            flags = self._global_flags()

            def body(xx, inp):
                p_i, cache_i, flag_i = inp
                y, nc, _ = block_apply(p_i, cfg, xx, self.kind,
                                       positions=positions,
                                       layer_is_global=flag_i,
                                       cache=cache_i, cache_index=index,
                                       decode=True, impl="xla",
                                       mrope_positions=mrope_positions)
                return y, nc
            x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache, flags))
        else:
            new_cache = []
            for i, bp in enumerate(params["blocks"]):
                x, nc, _ = block_apply(bp, cfg, x, self.kind,
                                       positions=positions,
                                       layer_is_global=self._is_global(i),
                                       cache=cache[i], cache_index=index,
                                       decode=True, impl="xla",
                                       mrope_positions=mrope_positions)
                new_cache.append(nc)

        x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        logits = (x @ head).astype(jnp.float32)
        return logits, new_cache

    # -- plan units for the Mimose collector --------------------------------
    def plan_units(self, params, batch) -> List[PlanUnit]:
        """Ordered plannable units.  Each unit's ``apply`` is a pure
        fn(unit_params, x) -> x at the *current* batch geometry, which the
        shuttling collector inspects abstractly (eval_shape + vjp)."""
        cfg = self.cfg
        units: List[PlanUnit] = []
        x, positions, mrope_positions = jax.eval_shape(
            lambda p, b: self._embed_inputs(p, b), params, batch)[0], None, None
        # recompute positions cheaply (concrete, shapes only matter)
        tokens = batch["tokens"]
        B, St = tokens.shape
        S = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if cfg.mrope:
            mrope_positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, None, :], (3, B, S))

        idx = 0
        if cfg.encoder_layers:
            F = batch["frames"].shape[1]
            enc_pos = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), (B, F))
            for i, bp in enumerate(params["encoder"]["blocks"]):
                def enc_fn(p, xx, _pos=enc_pos):
                    y, _, _ = block_apply(p, cfg, xx, "enc", positions=_pos,
                                          impl=self.attn_impl)
                    return y
                units.append(PlanUnit(f"enc{i}", idx, bp, enc_fn,
                                      signature=("enc",)))
                idx += 1

        enc_out_struct = None
        if cfg.encoder_layers:
            enc_out_struct = jnp.zeros(
                (B, batch["frames"].shape[1], cfg.d_model), self.dtype)
        # decoder units close over the encoder output: its geometry must be
        # part of the dedup signature or cross-attention residuals cached at
        # one frame count would be replayed at another
        enc_sig = (tuple(enc_out_struct.shape)
                   if enc_out_struct is not None else None)

        def _slice(a, s, e):
            # works for arrays and ShapeDtypeStructs (abstract dry-run)
            if isinstance(a, jax.ShapeDtypeStruct):
                return jax.ShapeDtypeStruct((e - s,) + a.shape[1:], a.dtype)
            return a[s:e]

        if cfg.remat_mode == "scan":
            for c, (s, e) in enumerate(self._chunk_bounds()):
                p_chunk = jax.tree_util.tree_map(
                    lambda a, _s=s, _e=e: _slice(a, _s, _e), params["blocks"])

                def chunk_fn(p, xx, _flag=self._chunk_flag(s, e)):
                    def body(carry, pi):
                        y, _, _ = block_apply(pi, cfg, carry, self.kind,
                                              positions=positions,
                                              layer_is_global=_flag,
                                              enc_out=enc_out_struct,
                                              mrope_positions=mrope_positions,
                                              impl=self.attn_impl)
                        return y, None
                    out, _ = jax.lax.scan(body, xx, p)
                    return out
                units.append(PlanUnit(
                    f"chunk{c}[{s}:{e}]", idx, p_chunk, chunk_fn,
                    signature=("chunk", self._chunk_flag(s, e), e - s,
                               enc_sig)))
                idx += 1
        else:
            for i, bp in enumerate(params["blocks"]):
                def blk_fn(p, xx, _i=i):
                    y, _, _ = block_apply(p, cfg, xx, self.kind,
                                          positions=positions,
                                          layer_is_global=self._is_global(_i),
                                          enc_out=enc_out_struct,
                                          mrope_positions=mrope_positions,
                                          impl=self.attn_impl)
                    return y
                units.append(PlanUnit(f"block{i}", idx, bp, blk_fn,
                                      signature=("block", self._is_global(i),
                                                 enc_sig)))
                idx += 1
        return units


def build_model(cfg: ModelConfig, attn_impl: str = "xla") -> LM:
    return LM(cfg, attn_impl=attn_impl)
