"""Mixture-of-Experts with expert parallelism over the ``model`` mesh axis.

Two execution paths, both inside ``shard_map`` so the collective pattern is
explicit in the lowered HLO (it is a roofline term we track):

* train/prefill (``decode=False``): tokens are sharded over
  (batch → data axes, sequence → model axis). Each device routes its local
  tokens, builds fixed-capacity per-expert buffers, and a pair of
  ``all_to_all`` collectives over the ``model`` axis moves tokens to the
  devices that own their experts and back (GShard-style EP, capacity drop).

* decode (``decode=True``): one token per request — too small to shard the
  sequence. Tokens are replicated over ``model``; every device evaluates
  only its local expert shard for all tokens and a ``psum`` combines.

* held experts (``MoEConfig.n_held`` > 0, ``moe_held``): one chip's
  share of expert parallelism. The chip holds experts ``[held_offset,
  held_offset + n_held)`` and computes their part of the result for every
  token routed to them, dropless: the routed slots sorted by expert, one
  grouped product per weight over the held experts, a gate-weighted
  scatter-add back. What the other chips' experts add is not computed.

Shared ("always-on") experts are a plain dense SwiGLU of width
``n_shared * d_expert`` applied outside this module (tensor-parallel).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.compat import shard_map
from repro.kernels.grouped_matmul import grouped_matmul
from repro.models.common import PD, dense_pd


EP_ALIGN = 16   # production model-axis size: expert stacks pad up to this


def padded_experts(cfg) -> int:
    """Expert-stack size: n_routed padded to a multiple of EP_ALIGN so the
    stack shards evenly over the model axis (e.g. qwen2-moe's 60 -> 64).
    The router never selects the dead slots; their buffers stay empty, so
    the math is exact (documented in DESIGN.md §5)."""
    e = cfg.moe
    if e.n_routed % EP_ALIGN == 0 or e.n_routed < EP_ALIGN:
        return e.n_routed
    return -(-e.n_routed // EP_ALIGN) * EP_ALIGN


def moe_pd(cfg):
    d = cfg.d_model
    e = cfg.moe
    E = padded_experts(cfg)
    scale_in = d ** -0.5
    scale_out = e.d_expert ** -0.5 / math.sqrt(2 * cfg.n_layers)
    return {
        "router": dense_pd(d, e.n_routed, spec=P(None, None), scale=scale_in),
        "w_gate": PD((E, d, e.d_expert), spec=P("model", None, None),
                     scale=scale_in),
        "w_up": PD((E, d, e.d_expert), spec=P("model", None, None),
                   scale=scale_in),
        "w_down": PD((E, e.d_expert, d), spec=P("model", None, None),
                     scale=scale_out),
    }


def _dp_axes(mesh):
    return tuple(a for a in mesh.axis_names if a != "model")


def _gates(x_flat, router_w, e):
    """Top-k routing over all ``n_routed`` experts: float32 logits, a
    softmax, the top k; the gates renormalised to sum to 1 where
    ``e.norm_topk``, then times ``e.routed_scale``. Returns gates (T,k)
    f32, ids (T,k) i32 and the probabilities (T,E)."""
    logits = (x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, e.top_k)
    if e.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if e.routed_scale != 1.0:
        gates = gates * e.routed_scale
    return gates, ids, probs


def _balance(counts, prob_sums, tokens: int, e):
    """The balance loss E * sum_e f_e * P_e (Switch; DeepSeek-V2's
    expert-level loss), f_e the share of the routed slots that chose
    expert e and P_e its mean probability, from per-sequence ``counts``
    and ``prob_sums`` (n, E) over ``tokens`` tokens a sequence: taken per
    sequence and averaged with ``e.seq_aux``, else over all of them."""
    if not e.seq_aux:
        tokens = tokens * counts.shape[0]
        counts = counts.sum(0, keepdims=True)
        prob_sums = prob_sums.sum(0, keepdims=True)
    f = counts / (tokens * e.top_k)
    p = prob_sums / tokens
    return e.n_routed * jnp.mean(jnp.sum(f * p, axis=-1))


def _route(x_flat, router_w, e):
    """Top-k routing (``_gates``). Returns gates (T,k) f32, ids (T,k) i32,
    aux-loss."""
    gates, ids, probs = _gates(x_flat, router_w, e)
    counts = jnp.zeros(e.n_routed).at[ids.reshape(-1)].add(1.0)
    aux = _balance(counts[None], probs.sum(0)[None], x_flat.shape[0], e)
    return gates, ids, aux


def _expert_ffn(w_gate, w_up, w_down, xs):
    """xs: (E_loc, C, d) -> (E_loc, C, d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", xs, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def moe_apply(p, x, cfg, mesh, *, decode: bool):
    """x: (B, S, d) global. Returns (out, aux_loss scalar)."""
    e = cfg.moe
    dp = _dp_axes(mesh)
    tp = mesh.shape["model"]
    if decode or x.shape[1] < tp:
        in_spec = P(dp, None, None)
        fn = partial(_moe_local_psum, cfg=cfg, tp=tp, dp=dp)
    else:
        in_spec = P(dp, "model", None)
        fn = partial(_moe_a2a, cfg=cfg, tp=tp, dp=dp)
    wspec = P("model", None, None)
    out, aux = shard_map(
        fn, mesh=mesh,
        in_specs=(in_spec, P(None, None), wspec, wspec, wspec),
        out_specs=(in_spec, P()),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, aux


def _moe_a2a(x, router_w, w_gate, w_up, w_down, *, cfg, tp, dp):
    """Per-device body, tokens sharded over (dp, model). EP via all_to_all."""
    e = cfg.moe
    B, S, d = x.shape
    T = B * S
    x_flat = x.reshape(T, d)
    gates, ids, aux = _route(x_flat, router_w, e)
    E = w_gate.shape[0] * tp          # padded expert-stack size
    C = max(4, int(math.ceil(T * e.top_k / e.n_routed
                             * e.capacity_factor)))

    ids_f = ids.reshape(-1)                       # (T*k,) all < n_routed
    gates_f = gates.reshape(-1)
    tok_f = jnp.repeat(jnp.arange(T), e.top_k)
    onehot = jax.nn.one_hot(ids_f, E, dtype=jnp.int32)
    pos_f = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1  # slot in expert
    keep = pos_f < C
    ids_safe = jnp.where(keep, ids_f, E)          # E -> dropped (mode=drop)
    pos_safe = jnp.where(keep, pos_f, 0)

    disp = jnp.full((E, C), T, jnp.int32)         # sentinel T = zero row
    disp = disp.at[ids_safe, pos_safe].set(tok_f, mode="drop")
    gate_ec = jnp.zeros((E, C), jnp.float32)
    gate_ec = gate_ec.at[ids_safe, pos_safe].set(gates_f, mode="drop")

    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, d), x_flat.dtype)], 0)
    xs = x_pad[disp]                              # (E, C, d)
    if tp > 1:
        xs = jax.lax.all_to_all(xs, "model", split_axis=0, concat_axis=1,
                                tiled=True)       # (E/tp, tp*C, d)
    ys = _expert_ffn(w_gate, w_up, w_down, xs)
    if tp > 1:
        ys = jax.lax.all_to_all(ys, "model", split_axis=1, concat_axis=0,
                                tiled=True)       # (E, C, d)
    out = jnp.zeros((T + 1, d), jnp.float32)
    out = out.at[disp].add(ys.astype(jnp.float32)
                           * gate_ec[..., None])
    out = out[:T].reshape(B, S, d).astype(x.dtype)
    aux = jax.lax.pmean(aux, dp + ("model",))
    return out, aux


def _moe_local_psum(x, router_w, w_gate, w_up, w_down, *, cfg, tp, dp):
    """Per-device body, tokens replicated over 'model'. Each device runs its
    local expert shard on all tokens; psum combines. Decode-sized T only."""
    e = cfg.moe
    B, S, d = x.shape
    T = B * S
    x_flat = x.reshape(T, d)
    gates, ids, aux = _route(x_flat, router_w, e)
    E_loc = w_gate.shape[0]
    offset = jax.lax.axis_index("model") * E_loc
    # (T, E_loc) combine weights for the local experts
    local_slot = ids - offset                     # (T, k)
    in_range = (local_slot >= 0) & (local_slot < E_loc)
    comb = jnp.zeros((T, E_loc), jnp.float32)
    comb = comb.at[jnp.arange(T)[:, None], jnp.where(in_range, local_slot, 0)
                   ].add(jnp.where(in_range, gates, 0.0))
    # evaluate every local expert on all tokens (T is decode-sized)
    h = _expert_ffn(w_gate, w_up, w_down,
                    jnp.broadcast_to(x_flat[None], (E_loc, T, d)))
    out = jnp.einsum("te,etd->td", comb, h.astype(jnp.float32))
    out = jax.lax.psum(out.astype(jnp.float32), "model")
    # tokens are replicated over 'model' here: aux only varies over dp
    aux = jax.lax.pmean(aux, dp) if dp else aux
    return out.reshape(B, S, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# held experts, dropless (one chip's share of expert parallelism)

def _live_rows(x, live):
    """``x`` with the rows past the held experts' groups set to 0; its
    gradient likewise (a select, so no value in those rows reaches the
    live ones, NaN included)."""
    return jnp.where(live, x, jnp.zeros((), x.dtype))


#: the counters ``moe_held`` returns, per step as the train step reports
#: them (``obs.moe.rows_here``, ``obs.moe.load_max_over_mean``)
HELD_STATS = ("moe_rows_here", "moe_load_max_over_mean")


def held_pd(cfg, n_layers: int):
    """The held experts of ``n_layers`` MoE layers: one (d, d_expert) or
    (d_expert, d) matrix per (layer, expert), stacked layer-major along
    one leading axis, (n_layers * n_held, ...). Each is a published
    tensor, so LARS scales each on its own (``PD.stack`` 1)."""
    d, e = cfg.d_model, cfg.moe
    n = n_layers * e.n_held
    scale_in = d ** -0.5
    scale_out = e.d_expert ** -0.5 / math.sqrt(2 * cfg.n_layers)
    return {
        "w_gate": PD((n, d, e.d_expert), spec=P(None, None, None),
                     scale=scale_in, stack=1),
        "w_up": PD((n, d, e.d_expert), spec=P(None, None, None),
                   scale=scale_in, stack=1),
        "w_down": PD((n, e.d_expert, d), spec=P(None, None, None),
                     scale=scale_out, stack=1),
    }


def moe_held(router_w, experts, x, cfg):
    """The held experts' part of a MoE layer, dropless. ``router_w`` (d,
    n_routed); ``experts`` {w_gate, w_up (n_held, d, f), w_down (n_held, f,
    d)}; x (B, S, d). Returns (out (B, S, d), balance loss, {``HELD_STATS``}).

    One sequence at a time (a scan over B), so that the static buffers
    hold one sequence's worst case and not the batch's: the router scores
    all ``n_routed`` experts (``_gates``); of the S * k routed slots, those
    of held experts are sorted by expert id to the front of a buffer of
    all S * k (the worst case: every slot held), the rest after them;
    ``kernels.grouped_matmul`` runs each weight's grouped product over the
    held experts' rows alone (its time follows those rows, not the
    buffer), and each row comes back to its token scaled by its gate (a
    scatter-add). Under the ``router``, ``dispatch``,
    ``experts`` and ``combine`` scopes. Each sequence is rematerialised
    in the backward pass.

    A grouped product leaves the rows past its groups as it finds them,
    in its result and in its data gradient alike; its weight gradient
    reads the groups' rows alone. So the gathered rows and the last
    product's result are masked to the live rows (``_live_rows``): the mask keeps
    those rows out of the result, and the gathered rows' mask keeps the
    data gradient's out of the tokens' gradient."""
    e = cfg.moe
    S, k = x.shape[1], e.top_k

    def sequence(_, xs):
        with jax.named_scope("router"):
            gates, ids, probs = _gates(xs, router_w, e)
        with jax.named_scope("dispatch"):
            local = ids.reshape(-1) - e.held_offset           # (S*k,)
            held = (local >= 0) & (local < e.n_held)
            key = jnp.where(held, local, e.n_held)
            order = jnp.argsort(key, stable=True)
            tok = order // k
            counts = jnp.zeros(e.n_held + 1, jnp.int32).at[key].add(1)
            n_here = S * k - counts[e.n_held]
            live = (jnp.arange(S * k) < n_here)[:, None]
            rows = _live_rows(xs[tok], live)                   # (S*k, d)
            sizes = counts[:e.n_held]
        with jax.named_scope("experts"):
            h = jax.nn.silu(grouped_matmul(rows, experts["w_gate"], sizes))
            h = h * grouped_matmul(rows, experts["w_up"], sizes)
            y = grouped_matmul(h, experts["w_down"], sizes)
        with jax.named_scope("combine"):
            g = jnp.where(held, gates.reshape(-1), 0.0)[order]
            y = _live_rows(y.astype(jnp.float32), live) * g[:, None]
            out = jnp.zeros(xs.shape, jnp.float32).at[tok].add(y)
        with jax.named_scope("router"):
            chose = jnp.zeros(e.n_routed).at[ids.reshape(-1)].add(1.0)
        return None, (out.astype(xs.dtype), chose, probs.sum(0), sizes)

    # each sequence is recomputed in the backward pass, so that no buffer
    # of the batch's slots is held between the passes
    _, (out, chose, prob_sums, sizes) = jax.lax.scan(
        jax.checkpoint(sequence), None, x)
    aux = _balance(chose, prob_sums, S, e)
    sizes = sizes.sum(0)
    n_here = jnp.sum(sizes)
    mean = jnp.maximum(n_here, 1) / e.n_held
    stats = {"moe_rows_here": n_here.astype(jnp.float32),
             "moe_load_max_over_mean": jnp.max(sizes) / mean}
    return out, aux, stats
