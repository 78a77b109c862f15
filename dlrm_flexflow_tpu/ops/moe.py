"""Sparse mixture of experts, as one chip of an expert-parallel group runs it.

The router scores every token against ALL `num_experts` experts (fp32),
keeps the `top_k` largest scores and, with `norm_topk`, divides them by
their sum. The model says which router it has (`scoring`):

    softmax  scores are softmax(x W_r); chosen = top-k of them (Qwen3-Next)
    sigmoid  scores are sigmoid(x W_r); chosen = top-k of score + bias, the
             weights the bare scores of the chosen, normalised and scaled
             by `routed_scale` (DeepSeek-V3's `noaux_tc`, GLM-4.7's)

This op holds `experts_held` of the experts, those numbered
`expert_offset` .. `expert_offset + experts_held - 1`, and computes their
part of the result:

    routed = sum over e in (top-k and held) of w_e * E_e(x)
    out    = routed + sigmoid(x . w_sg) * shared_expert(x)

(`shared_gate=False`: the shared expert is added as it is; `shared_dim=0`:
there is none, no parameter, no scope, no FLOP of it, LFM2's.) The model says
which form its experts have (`activation`), routed and shared alike:

    swiglu   E(x) = down(silu(gate x) * up x), three matrices (Qwen3-Next,
             GLM-4.7)
    relu2    E(x) = down(relu(up x)^2), two: no `w_gate`, no `shared_gate`
             weight (Nemotron-H)

The weights w_e are normalised over all `top_k` chosen experts, held here
or not; what the experts held elsewhere would add is theirs to compute (on
their chips, behind an exchange this op does not have yet), and is left out
here. With every expert held (`experts_held == num_experts`) the op is the
whole layer.

Dropless: every (token, held expert) pair is computed, at any imbalance.
The pairs are sorted by expert, so a held expert's pairs are one stretch of
rows, and the stretches are walked a chunk of `chunk_rows` rows at a time:
a chunk gathers its rows' tokens, multiplies them with ITS expert's
matrices (plain products, one expert a chunk), scales by the pair weights
and writes its rows, side by side, into a buffer of all sorted pairs; the
result is each token's sum over the rows of its held pairs (a gather by the
inverse of the sort). The walk is a loop whose trip count is the step's own
number of chunks, so a step with few pairs computes few rows, and the worst
case (every token picks held experts, `num_experts / experts_held` times
the expected) is only more trips. Reverse-mode autodiff cannot follow a
loop of unknown length, so forward and backward are written out
(`custom_vjp`): the backward walks the same chunks, recomputes each, adds an
expert's weight gradient into that expert's slab and writes the rows'
input gradients side by side, to be summed a token by the same gather. No
scatter anywhere: a chunk's rows past its expert's last pair are computed
and masked, and where they lie over the next expert's rows the next chunk
writes over them.

On one TPU, where a whole expert's matrices fit the chip's VMEM beside their
gradient and the walk's plan its scalar memory
(`ops/pallas/moe_kernel.py:grid_walk_ok`: Qwen3-Next's 2,048 x 512 x 3 do,
GLM-4.7's 2,048 x 1,536 x 3 and Nemotron's F = 1,856 do not), the walk is
not an XLA loop but the grid of a Pallas kernel, one call a pass
(`_routed_grid`): a grid step is a trip of `moe_kernel.ROWS` rows, fetches
its rows' tokens a DMA a row and its expert's matrices once an expert, and
writes its rows into its own block of a buffer in which an expert's stretch
starts at a multiple of the trip; the backward keeps an expert's weight
gradient in VMEM across that expert's trips. The sort, the plan's
arithmetic, `combine`, the dtypes of every product and the counters are the
same; everywhere else the loop below, compiled to what it was.

`combine`, in both passes of both walks, is likewise a Pallas gather-sum
where the rule allows it (`ops/pallas/combine_kernel.py:combine_ok`: one
TPU, bfloat16 rows of whole lane tiles, the landing buffers in the chip's
VMEM and the plan in its scalar memory): a token's held pairs' rows fetched by DMA, the pairs
held elsewhere not at all, and summed in VMEM in the order the XLA form adds
them, to the bit but for the sign of a zero; everywhere else the k gathers.
The op records the route as the step is traced (`combine_kernel`, in
`FFModel.expert_stats()`).

Counters (cumulative, in `op_state`, never read back by the step):
`tokens` seen, `pairs` routed to each held expert, `rows` the FORWARD's
trips computed (`chunk_rows` a trip of the loop, `moe_kernel.ROWS` of the
kernel). rows - sum(pairs) carried no pair. The loop's backward walks the
same trips; the kernel's gives a held expert with no pair one trip more
(its gradient block must be written, as zeros), which `rows` leaves out.

With `balance_rate` gamma > 0 the op also keeps, in `op_state`, the
router's correction `bias` (num_experts,) and a cumulative `load`, the
pairs routed to EVERY expert, held here or not. No gradient trains the
bias; the step does: after its forward, b_e += gamma * sign(mean(c) - c_e)
with c the step's own load on this chip's tokens (a deployment would sum c
over its data-parallel ranks first). The next step reads it back: it is
the one `op_state` entry that changes the result. An op that does not ask
has neither, and compiles to what it compiled to without them.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.initializers import DEFAULT_KERNEL_INIT, ZeroInitializer
from ..core.op import Op, ParamDef
from .pallas import combine_kernel, moe_kernel

# Rows of one expert's sorted pairs a trip computes. A trip's cost is mostly
# fixed (on the v5e at Qwen3-Next's widths ~0.6 ms over forward, recomputation
# and backward, whatever its rows), so the trips set the walk's time. With ~160
# pairs an expert, 128 / 256 / 512 rows gave a step of 593-608 / 550-578 / 535
# ms with 9% / 17% / 30% of the rows carrying no pair (chip runs, PR 26); at
# 512 nearly every expert is one trip whatever the seed's routing, where at
# 256 the number of two-trip experts moved the step by 3% from seed to seed.
# At a deployment's 2,560 pairs an expert 512 pads under 10%.
CHUNK_ROWS = 512


# an expert's form -> its matrices, as `w_<name>` / `shared_<name>`
FORMS = {"swiglu": ("gate", "up", "down"), "relu2": ("up", "down")}


def _silu_mul(g, u):
    return jax.nn.silu(g) * u


def _ffn(cdt, act, xs, ws):
    """One expert of form `act` on rows xs (rows, D) in `cdt`; ws its fp32
    matrices, (gate, up, down) (D, F), (D, F), (F, D) for "swiglu", (up,
    down) for "relu2". -> (rows, D) fp32."""
    def mm(a, w):
        return jnp.dot(a, w.astype(cdt), preferred_element_type=jnp.float32)

    if act == "swiglu":
        h = _silu_mul(mm(xs, ws[0]), mm(xs, ws[1]))
    else:
        h = jnp.square(jax.nn.relu(mm(xs, ws[0])))
    return mm(h.astype(cdt), ws[-1])


def _walk_plan(rows, counts):
    """The walk over the held experts' stretches of sorted pairs, `rows`
    rows a chunk: (chunks up to and including each expert (held,), each
    expert's first sorted row (held,), number of chunks). Expert e's
    stretch takes ceil(counts[e] / rows) chunks."""
    last = jnp.cumsum((counts + rows - 1) // rows)
    return last, jnp.cumsum(counts) - counts, last[-1]


def _chunk(rows, j, plan, counts, order):
    """Chunk j of the walk: (its expert, the pair of each of its rows
    (rows,), its first row, which rows carry a pair of its expert)."""
    last, start, _ = plan
    e = jnp.searchsorted(last, j, side="right").astype(jnp.int32)
    per = (counts[e] + rows - 1) // rows
    first = start[e] + (j - (last[e] - per)) * rows
    valid = first + jnp.arange(rows) < start[e] + counts[e]
    return e, lax.dynamic_slice(order, (first,), (rows,)), first, valid


def _expert_ffn(cdt, act, xs, ws, w_row):
    """One expert on a chunk's rows, each scaled by its pair's weight
    w_row (rows,): the rows' weighted outputs, fp32."""
    return _ffn(cdt, act, xs, ws) * w_row[:, None]


def _sum_pairs(rows_buf, pos, held_pair, top_k, kernel=False,
               interpret=False):
    """Each token's sum over the rows of its held pairs, fp32: rows_buf
    (cap, D) is indexed by sorted position, pos and held_pair (T * k,) by
    pair. One choice of the k at a time: a gather of T rows and an add; with
    `kernel` (`combine_kernel.combine_ok` says where) one Pallas gather-sum
    that fetches the held pairs' rows alone, the same sum bit for bit but
    for the sign of a zero."""
    if kernel:
        return combine_kernel.gather_sum(rows_buf, pos, held_pair, top_k,
                                         interpret)
    pos, held = pos.reshape(-1, top_k), held_pair.reshape(-1, top_k)
    out = 0.0
    for j in range(top_k):
        mine = jnp.take(rows_buf, pos[:, j], axis=0).astype(jnp.float32)
        out = out + jnp.where(held[:, j, None], mine, 0.0)
    return out


def _sorted_position(order, pairs):
    """Where the sort put each pair: the inverse of `order`'s permutation
    of the `pairs` pair indices (its padding left out)."""
    return jnp.argsort(order[:pairs])


def _add_slab(acc, e, g):
    """acc[e] += g on one expert's slab, in place (a dynamic slice, not a
    scatter, which XLA would run a row at a time)."""
    at = (e,) + (0,) * g.ndim
    slab = lax.dynamic_slice(acc, at, (1,) + g.shape)
    return lax.dynamic_update_slice(acc, slab + g[None], at)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 10))
def _routed(rows, top_k, cdt, act, xt, ws, pair_w, order, counts,
            held_pair, combine=False):
    """The held experts' part of the result. xt (T, D); ws the experts'
    matrices as `_ffn` takes them, each (held, ...) fp32; pair_w (T * k,)
    fp32; order (T * k + rows,) pair indices sorted by held expert, the
    pairs of experts held elsewhere last, then padding; counts (held,)
    pairs a held expert; held_pair (T * k,) bool; `combine` whether both
    passes' `combine` is the kernel. -> (T, D) fp32."""
    plan = _walk_plan(rows, counts)

    def trip(j, buf):
        e, idx, first, _ = _chunk(rows, j, plan, counts, order)
        with jax.named_scope("dispatch"):
            xs = jnp.take(xt, idx // top_k, axis=0).astype(cdt)
        with jax.named_scope("experts"):
            y = _expert_ffn(cdt, act, xs, tuple(w[e] for w in ws),
                            jnp.take(pair_w, idx))
        return lax.dynamic_update_slice(buf, y.astype(cdt), (first, 0))

    buf = lax.fori_loop(0, plan[2], trip,
                        jnp.zeros((order.size, xt.shape[1]), cdt))
    with jax.named_scope("combine"):
        return _sum_pairs(buf, _sorted_position(order, held_pair.size),
                          held_pair, top_k, combine)


def _routed_fwd(rows, top_k, cdt, act, xt, ws, pair_w, order, counts,
                held_pair, combine=False):
    out = _routed(rows, top_k, cdt, act, xt, ws, pair_w, order, counts,
                  held_pair, combine)
    return out, (xt, ws, pair_w, order, counts, held_pair)


def _routed_bwd(rows, top_k, cdt, act, combine, res, ct):
    xt, ws, pair_w, order, counts, held_pair = res
    plan = _walk_plan(rows, counts)

    def trip(j, carry):
        dws, dx_buf, dw_buf = carry
        e, idx, first, valid = _chunk(rows, j, plan, counts, order)
        tok = idx // top_k
        with jax.named_scope("dispatch"):
            xs = jnp.take(xt, tok, axis=0).astype(cdt)
            dy = jnp.where(valid[:, None], jnp.take(ct, tok, axis=0), 0.0)
        with jax.named_scope("experts"):
            _, vjp = jax.vjp(partial(_expert_ffn, cdt, act), xs,
                             tuple(w[e] for w in ws), jnp.take(pair_w, idx))
            dxs, dw, dw_row = vjp(dy)
        return (tuple(_add_slab(acc, e, g) for acc, g in zip(dws, dw)),
                lax.dynamic_update_slice(dx_buf, dxs.astype(cdt), (first, 0)),
                lax.dynamic_update_slice(dw_buf, dw_row, (first,)))

    dws, dx_buf, dw_buf = lax.fori_loop(
        0, plan[2], trip,
        (tuple(jnp.zeros_like(w) for w in ws),
         jnp.zeros((order.size, xt.shape[1]), cdt),
         jnp.zeros((order.size,), jnp.float32)))
    with jax.named_scope("combine"):
        pos = _sorted_position(order, held_pair.size)
        dxt = _sum_pairs(dx_buf, pos, held_pair, top_k,
                         combine).astype(xt.dtype)
        dpair_w = jnp.where(held_pair, jnp.take(dw_buf, pos), 0.0)
    return dxt, dws, dpair_w, None, None, None


_routed.defvjp(_routed_fwd, _routed_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 10))
def _routed_grid(rows, top_k, cdt, interpret, xt, ws, pair_w, order, counts,
                 held_pair, combine=False):
    """`_routed` with the walk as the grid of a Pallas kernel
    (`ops/pallas/moe_kernel.py`; `moe_kernel.grid_walk_ok` says where):
    the same arguments but for the form, which the number of matrices
    says, and the same result. A trip writes ITS block of the sorted-rows
    buffer, so an expert's stretch starts at a multiple of `rows` there
    and `combine` gathers by the aligned position."""
    with jax.named_scope("dispatch"):
        pos = _sorted_position(order, held_pair.size)
    buf, plan = moe_kernel.experts_fwd(rows, top_k, cdt, xt, ws, pair_w,
                                       order, pos, counts, interpret)
    with jax.named_scope("combine"):
        return _sum_pairs(buf, moe_kernel.aligned(plan, pos), held_pair,
                          top_k, combine, interpret)


def _routed_grid_fwd(rows, top_k, cdt, interpret, xt, ws, pair_w, order,
                     counts, held_pair, combine=False):
    out = _routed_grid(rows, top_k, cdt, interpret, xt, ws, pair_w, order,
                       counts, held_pair, combine)
    return out, (xt, ws, pair_w, order, counts, held_pair)


def _routed_grid_bwd(rows, top_k, cdt, interpret, combine, res, ct):
    xt, ws, pair_w, order, counts, held_pair = res
    with jax.named_scope("dispatch"):
        pos = _sorted_position(order, held_pair.size)
    dx_buf, dw_buf, dws, plan = moe_kernel.experts_bwd(
        rows, top_k, cdt, xt, ws, pair_w, order, pos, counts, ct, interpret)
    with jax.named_scope("combine"):
        pos = moe_kernel.aligned(plan, pos)
        dxt = _sum_pairs(dx_buf, pos, held_pair, top_k, combine,
                         interpret).astype(xt.dtype)
        dpair_w = jnp.where(held_pair, jnp.take(dw_buf, pos), 0.0)
    return dxt, dws, dpair_w, None, None, None


_routed_grid.defvjp(_routed_grid_fwd, _routed_grid_bwd)


class MoE(Op):
    type_name = "MoE"
    recompute = True     # the backward recomputes the block's insides

    def __init__(self, model, x, num_experts: int, top_k: int,
                 expert_dim: int, shared_dim: int,
                 experts_held: Optional[int] = None, expert_offset: int = 0,
                 norm_topk: bool = True, scoring: str = "softmax",
                 routed_scale: float = 1.0, shared_gate: bool = True,
                 balance_rate: float = 0.0, kernel_initializer=None,
                 name: Optional[str] = None, activation: str = "swiglu"):
        held = num_experts if experts_held is None else experts_held
        if not 0 < top_k <= num_experts:
            raise ValueError("top_k must lie in 1..num_experts")
        if not (0 < held and 0 <= expert_offset
                and expert_offset + held <= num_experts):
            raise ValueError(
                f"experts {expert_offset}..{expert_offset + held - 1} are "
                f"not among the {num_experts} the router scores")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router scoring {scoring!r}")
        if balance_rate and scoring != "sigmoid":
            raise ValueError("the balance bias corrects sigmoid scores")
        if activation not in FORMS:
            raise ValueError(f"unknown expert form {activation!r}")
        super().__init__(model, [x], name)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.expert_dim, self.shared_dim = int(expert_dim), int(shared_dim)
        self.experts_held, self.expert_offset = int(held), int(expert_offset)
        self.norm_topk = bool(norm_topk)
        self.scoring, self.routed_scale = scoring, float(routed_scale)
        self.shared_gate = bool(shared_gate)
        self.balance_rate = float(balance_rate)
        self.activation = activation
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT()
        self.tokens = 1
        for n in x.shape[:-1]:
            self.tokens *= int(n)
        self.chunk_rows = CHUNK_ROWS
        self.combine_kernel = False     # set as the step is traced
        self.outputs = [self._make_output(x.shape, x.dtype)]

    def param_defs(self) -> Dict[str, ParamDef]:
        d = self.inputs[0].shape[-1]
        n, f, fs = self.experts_held, self.expert_dim, self.shared_dim
        init, f32 = self.kernel_initializer, jnp.float32
        defs = {
            "router": ParamDef((d, self.num_experts), f32, init),
            "w_gate": ParamDef((n, d, f), f32, init),
            "w_up": ParamDef((n, d, f), f32, init),
            "w_down": ParamDef((n, f, d), f32, init),
        }
        if fs:
            defs.update(shared_gate=ParamDef((d, fs), f32, init),
                        shared_up=ParamDef((d, fs), f32, init),
                        shared_down=ParamDef((fs, d), f32, init))
            if self.shared_gate:
                defs["shared_router"] = ParamDef((d,), f32, init)
        if "gate" not in FORMS[self.activation]:
            defs.pop("w_gate")
            defs.pop("shared_gate", None)
        return defs

    def state_defs(self) -> Dict[str, ParamDef]:
        zero, i32 = ZeroInitializer(), jnp.int32
        defs = {"tokens": ParamDef((), i32, zero),
                "pairs": ParamDef((self.experts_held,), i32, zero),
                "rows": ParamDef((), i32, zero)}
        if self.balance_rate:
            defs["bias"] = ParamDef((self.num_experts,), jnp.float32, zero)
            defs["load"] = ParamDef((self.num_experts,), i32, zero)
        return defs

    def route(self, params, xt, bias=None):
        """(weights (T, k) fp32, experts (T, k) int32) of every token.
        `bias` (num_experts,), the sigmoid router's correction, moves the
        choice and never the weights."""
        logits = jnp.dot(xt.astype(jnp.float32), params["router"],
                         precision=lax.Precision.HIGHEST)
        if self.scoring == "softmax":
            top_p, top_e = lax.top_k(jax.nn.softmax(logits, axis=-1),
                                     self.top_k)
            if self.norm_topk:
                top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
            return top_p, top_e
        scores = jax.nn.sigmoid(logits)
        _, top_e = lax.top_k(scores if bias is None else scores + bias,
                             self.top_k)
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
        if self.norm_topk:
            top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
        return top_p * self.routed_scale, top_e

    def apply_with_state(self, params, state, xs, *, training=False,
                         rng=None):
        (x,) = xs
        d = x.shape[-1]
        xt = x.reshape(-1, d)
        held, cdt, f32 = self.experts_held, self.model.compute_dtype, \
            jnp.float32
        with jax.named_scope("router"):
            # without a bias `route` is called as it always was: tests and
            # the benchmark's fault injections replace that form
            pair_w, pair_e = (self.route(params, xt, state["bias"])
                              if self.balance_rate
                              else self.route(params, xt))
        with jax.named_scope("dispatch"):
            local = pair_e.reshape(-1) - self.expert_offset
            # the pairs of experts held elsewhere get the key `held` and
            # sort behind every held expert's
            key = jnp.where((local >= 0) & (local < held), local, held)
            order = jnp.argsort(key).astype(jnp.int32)
            # a chunk reads whole: the last one may read into the padding
            order = jnp.pad(order, (0, self.chunk_rows))
            counts = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
        names = FORMS[self.activation]
        ws = tuple(params[f"w_{n}"] for n in names)
        # the walk as a kernel's grid where the chip and the shapes allow
        # it, else as the XLA loop: one rule of what can be observed here
        grid = moe_kernel.grid_walk_ok(self.model, xt, ws, order)
        rows = moe_kernel.ROWS if grid else self.chunk_rows
        # and `combine` as the gather-sum kernel, by the same kind of rule;
        # the route is recorded as the step is traced (`expert_stats`)
        self.combine_kernel = combine_kernel.combine_ok(
            self.model, xt.shape[0], self.top_k, d, cdt)
        if grid:
            routed = _routed_grid(rows, self.top_k, cdt, False, xt, ws,
                                  pair_w.reshape(-1), order, counts,
                                  key < held, self.combine_kernel)
        else:
            routed = _routed(rows, self.top_k, cdt, self.activation, xt, ws,
                             pair_w.reshape(-1), order, counts, key < held,
                             self.combine_kernel)
        if self.shared_dim:
            with jax.named_scope("shared"):
                shared = _ffn(cdt, self.activation, xt.astype(cdt),
                              tuple(params[f"shared_{n}"] for n in names))
                if self.shared_gate:
                    gate = jax.nn.sigmoid(jnp.sum(
                        xt.astype(f32) * params["shared_router"], axis=-1,
                        keepdims=True))
                    shared = gate * shared
            routed = routed + shared
        new_state = {"tokens": state["tokens"] + xt.shape[0],
                     "pairs": state["pairs"] + counts,
                     "rows": state["rows"] + rows * _walk_plan(
                         rows, counts)[2]}
        if self.balance_rate:
            with jax.named_scope("balance"):
                load = jnp.sum(pair_e.reshape(-1, 1) == jnp.arange(
                    self.num_experts), axis=0, dtype=jnp.int32)
                new_state["load"] = state["load"] + load
                new_state["bias"] = state["bias"] + self.balance_rate * \
                    jnp.sign(jnp.mean(load.astype(f32)) - load)
        return [routed.reshape(x.shape).astype(x.dtype)], new_state

    def apply(self, params, xs, *, training=False, rng=None):
        raise RuntimeError("MoE uses apply_with_state")

    def flops_per_sample(self) -> float:
        tokens = self.tokens / max(self.outputs[0].shape[0], 1)
        d = self.outputs[0].shape[-1]
        pairs = self.top_k * self.experts_held / self.num_experts
        products = len(FORMS[self.activation])
        return tokens * (2.0 * d * self.num_experts
                         + 2.0 * products * d * (pairs * self.expert_dim
                                                 + self.shared_dim))
