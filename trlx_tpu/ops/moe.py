"""A dropless top-k expert layer: float32 routing, token copies sorted by
expert, a grouped matrix multiplication over the sorted rows, a weighted
float32 combine. The work follows the routed rows: ``k / E`` of computing
every expert on every token, at every token count, forward and backward.

The layer (OLMoE, Muennighoff et al. 2024, section 2; no token is ever
dropped and no capacity exists):

    p = softmax_f32(h W_r)                       over E experts
    (p_1..p_k, e_1..e_k) = top_k(p)              p_j kept as they are unless
                                                 ``norm_topk`` divides them
                                                 by their sum
    y = sum_j p_j * W_down[e_j]( silu(W_gate[e_j] h) * W_up[e_j] h )

How it is computed, all shapes static:

1. ``moe_router``: the logits and the softmax in float32, ``lax.top_k``.
2. ``moe_dispatch``: the ``N * k`` token copies are ordered by expert with
   one stable ``argsort``; ``group_sizes[e]`` counts the copies of expert
   ``e`` (they sum to ``N * k``: nothing is dropped). Rows are gathered
   into that order.
3. ``moe_experts``: the **grouped matrix multiplication**
   (:func:`grouped_matmul`, ``jax.lax.ragged_dot``): rows
   ``[offset_e, offset_e + group_sizes[e])`` are multiplied with expert
   ``e``'s matrix. On the TPU it compiles to one Mosaic kernel
   (``ragged-dot`` in a trace) that visits only the (row tile, expert)
   pairs that hold rows, so a prefill of 32768 rows is compute-bound on
   ``rows x 3 D F`` and a decode step of 256 rows reads each *touched*
   expert once. Gate and up are two calls on the same sorted rows (the
   parameter tree keeps ``w_gate`` and ``w_up`` apart as the checkpoint
   does; joining them per call would copy 537 MB a layer), down is the
   third. On the CPU the same primitive lowers to masked dense products
   (tests only).
4. ``moe_combine``: rows return to token order through the inverse
   permutation and the ``k`` copies of a token are summed in float32 under
   their routing weights.

Both permutations are gathers forward *and* backward (a permutation's
transpose is its inverse), so the backward pass scatters nothing.

On an ``ep`` mesh (:func:`expert_layer` with ``mesh``) the experts' ``[E]``
axis is sharded: inside a ``shard_map`` every rank holds the tokens of its
data shard (they are replicated over ``ep`` outside the layer, as the rest
of the block computes them), orders them so that *its own* experts' rows
come first, runs the same grouped multiplication over those rows alone,
and a ``psum`` over ``ep`` adds the ranks' partial sums - the gather of
tokens and the scatter of the sum folded into the one all-reduce the
replicated attention that follows needs anyway. Still dropless.

**One rank's share without a mesh** (:func:`expert_layer` with
``first_expert`` and fewer expert matrices than the router has outputs):
the same ordering and masking with nothing to sum over - the layer is told
which experts it holds (``first_expert .. first_expert + w_gate.shape[0]``
of the router's ``E``), routes over all ``E``, and returns the part of the
result its own experts give. What the absent experts would have added is
left out, and nothing stands in for the exchange: this is one chip of an
``ep`` group measured alone (a configuration whose file states that
deployment). ``shared`` is a float32 term added before the cast (a shared
expert); on an ``ep`` mesh it is refused by name: nothing runs it there.

**A router of the caller's own** (:func:`expert_layer` with ``routing``): a
family whose router is not one matrix on the block's input (an MLP, a carry
from the layer before, a bias that moves the choice and not the weight)
computes its :class:`Routing` itself and hands it over; steps 2-4 are the
same. One such router lives here because it is one matrix after all:
:func:`route_group_limited`, sigmoid scores under a selection bias, the
choice limited to the best groups of experts. A **skip** choice rides on the share above: a router ``E + 1`` wide
over ``E`` held experts, whose last index no expert holds, so its copies
are multiplied with nothing and contribute exactly zero
(:func:`routing_stats` counts them as ``skip_share``). Off a mesh only.

**A plain expert** (:func:`expert_layer` with ``w_gate`` None and
``activation`` naming the nonlinearity): ``W_down act(W_up h)``, two matrices
an expert and no gate, through the same dispatch, grouped multiplication and
combine (two calls on the sorted rows where the gated form makes three).
**Rows of the caller's width**: with a finished ``routing`` the layer never
reads the width of ``h``, so a family whose experts work in a latent of the
stream (the router reads the stream, the experts its projection) hands the
latent rows over and gets rows of ``w_down``'s width back; what is that wide
in the model's own width (a shared expert) is the caller's to add after the
way back up, not ``shared``'s.

Training forwards sow ``aux_loss`` (load balancing over the top-k
assignments, ``E * sum_e f_e P_e`` with ``f_e`` the copies routed to ``e``
per token and ``P_e`` the mean router probability; uniform routing gives
``k``), ``router_z`` and ``max_load`` into the ``moe_losses`` collection;
:func:`moe_loss_summary` and :func:`apply_router_penalty` are the one copy
both MoE families (``models/olmoe.py``, ``models/gpt2_moe.py``) and both
trainers use.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


class Routing(NamedTuple):
    logits: jax.Array  # [N, E] float32
    probs: jax.Array  # [N, E] float32
    weights: jax.Array  # [N, k] float32, the combine weights
    experts: jax.Array  # [N, k] int32


def route(h: jax.Array, router_w: jax.Array, k: int, norm_topk: bool = False) -> Routing:
    """Float32 routing of ``h`` [N, D] over ``router_w`` [D, E]."""
    logits = jnp.dot(
        h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return Routing(logits, probs, weights, experts.astype(jnp.int32))


def route_group_limited(h: jax.Array, router_w: jax.Array, bias: jax.Array, k: int, *,
                        n_group: int, topk_group: int, scale: float = 1.0) -> Routing:
    """Float32 routing of ``h`` [N, D] over ``router_w`` [D, E] by sigmoid
    scores, a selection bias and a limit on the groups a token may reach
    (DeepSeek-V3, section 2.1.2 and its auxiliary-loss-free balancing):

        s = sigmoid(h W_r);  s' = s + bias          the bias moves the choice only
        E experts in n_group groups of E / n_group; a group's score is the
        sum of its two largest s'; the topk_group best groups stay
        (e_1..e_k) = the k largest s' among the experts of those groups
        w_j = scale * s[e_j] / (sum_j s[e_j] + 1e-20)

    A dropped group's scores are masked with ``-inf``: none of its experts
    can be chosen whatever the bias's sign. ``probs`` are the ``E`` sigmoid
    scores (they do not sum to 1), ``logits`` what they are the sigmoid of."""
    N, E = h.shape[0], router_w.shape[-1]
    if E % n_group or not 0 < topk_group <= n_group or k > topk_group * (E // n_group):
        raise ValueError(
            f"{E} experts do not divide into {n_group} groups of which "
            f"{topk_group} hold the {k} a token takes"
        )
    logits = jnp.dot(
        h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = jax.nn.sigmoid(logits)
    biased = scores + bias.astype(jnp.float32)
    grouped = biased.reshape(N, n_group, E // n_group)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, topk_group)
    stays = jnp.zeros((N, n_group), bool).at[jnp.arange(N)[:, None], kept].set(True)
    limited = jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(N, E)
    _, experts = jax.lax.top_k(limited, k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return Routing(logits, scores, weights, experts.astype(jnp.int32))


# -- permutations whose backward pass is a gather too ---------------------- #


@jax.custom_vjp
def _take_copies(x, token_of, inverse):
    """``x[token_of]``: row ``i`` of the result is the token that sorted copy
    ``i`` belongs to. ``inverse`` (sorted position of copy ``n * k + j``)
    is only read by the backward pass."""
    return x[token_of]


def _take_copies_fwd(x, token_of, inverse):
    return x[token_of], (inverse, x.shape[0])


def _take_copies_bwd(res, g):
    inverse, n = res
    return g[inverse].reshape(n, -1, g.shape[-1]).sum(axis=1).astype(g.dtype), None, None


_take_copies.defvjp(_take_copies_fwd, _take_copies_bwd)


@jax.custom_vjp
def _permute(y, perm, inverse):
    """``y[perm]`` for a permutation ``perm`` whose inverse is ``inverse``."""
    return y[perm]


def _permute_fwd(y, perm, inverse):
    return y[perm], inverse


def _permute_bwd(inverse, g):
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def sort_by_expert(experts: jax.Array, num_experts: int, first_expert=0):
    """Order the ``N * k`` copies by expert. Returns ``(order, inverse,
    group_sizes)``: ``order[i]`` is the copy (``n * k + j``) at sorted row
    ``i``, ``inverse`` its inverse permutation, ``group_sizes[e]`` the rows
    of expert ``(first_expert + e) % E`` - with ``first_expert`` a rank's
    own experts come first (the ``ep`` path). Σ ``group_sizes`` = N·k."""
    flat = (experts.reshape(-1) - first_expert) % num_experts
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32)
    )
    group_sizes = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    return order, inverse, group_sizes


def grouped_matmul(rows: jax.Array, weights: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``rows`` [M, K] sorted by group, ``weights`` [G, K, N]: rows of group
    ``g`` times ``weights[g]``; rows past Σ ``group_sizes`` are unspecified
    (callers mask them). One kernel on the TPU, forward and each of its two
    transposes."""
    return jax.lax.ragged_dot(
        rows, weights, group_sizes, preferred_element_type=rows.dtype
    )


ACTIVATIONS = {"relu2": lambda x: jnp.square(jax.nn.relu(x))}  # the plain expert's, by the name a config gives it


def _experts_on_sorted(rows, w_gate, w_up, w_down, group_sizes, activation=None):
    with jax.named_scope("moe_experts"):
        if w_gate is None:  # a plain expert: no gate, the caller's activation
            up = grouped_matmul(rows, w_up, group_sizes)
            return grouped_matmul(ACTIVATIONS[activation](up), w_down, group_sizes)
        gate = grouped_matmul(rows, w_gate, group_sizes)
        up = grouped_matmul(rows, w_up, group_sizes)
        return grouped_matmul(jax.nn.silu(gate) * up, w_down, group_sizes)


def _apply_routed(h, routing: Routing, w_gate, w_up, w_down, dtype,
                  num_experts: int, first_expert=0, activation=None):
    """The expert layer after routing, over the experts ``w_*`` hold
    (all ``E`` of them, or a rank's ``E / ep`` starting at
    ``first_expert``): [N, D] in ``h``'s token order, float32."""
    N, k = routing.experts.shape
    local = w_up.shape[0]
    with jax.named_scope("moe_dispatch"):
        order, inverse, sizes = sort_by_expert(routing.experts, num_experts, first_expert)
        rows = _take_copies(h.astype(dtype), order // k, inverse)
    out = _experts_on_sorted(
        rows, None if w_gate is None else w_gate.astype(dtype), w_up.astype(dtype),
        w_down.astype(dtype), sizes[:local], activation,
    )
    with jax.named_scope("moe_combine"):
        if local < num_experts:
            # rows of other ranks' experts were multiplied with nothing
            mine = jnp.arange(N * k) < jnp.sum(sizes[:local])
            out = jnp.where(mine[:, None], out, jnp.zeros((), out.dtype))
        back = _permute(out, inverse, order).reshape(N, k, -1)
        return jnp.einsum(
            "nkd,nk->nd", back.astype(jnp.float32), routing.weights,
            precision=jax.lax.Precision.HIGHEST,
        )


def routing_stats(routing: Routing, num_experts: int, first_expert: int = 0,
                  held: Optional[int] = None, skip: Optional[int] = None) -> Dict[str, jax.Array]:
    """What a step's routing looked like, as device scalars that ride in
    outputs the caller fetches anyway: ``experts_touched`` (distinct
    experts with at least one row), ``max_load`` (the busiest expert's
    share of the rows; ``1 / E`` is perfect balance) and ``rows_routed``
    (``N * k``). Where the layer holds ``held < num_experts`` experts from
    ``first_expert`` on, the first two are over the held experts alone
    (what this chip reads and multiplies) and a fourth figure,
    ``rows_here_share``, is the share of the routed copies whose expert is
    held here. Where the router's index ``skip`` is a choice of no expert
    at all, what is not held is that choice and not another chip's: the
    fourth figure is ``skip_share``, the share of the copies that chose it."""
    N, k = routing.experts.shape
    counts = jnp.zeros((num_experts,), jnp.int32).at[routing.experts.reshape(-1)].add(1)
    stats = {}
    if skip is not None:
        stats["skip_share"] = counts[skip].astype(jnp.float32) / (N * k)
    if held is not None and held < num_experts:
        counts = jnp.roll(counts, -first_expert)[:held]
        if skip is None:
            stats["rows_here_share"] = jnp.sum(counts).astype(jnp.float32) / (N * k)
    return {
        "experts_touched": jnp.sum(counts > 0).astype(jnp.float32),
        "max_load": jnp.max(counts).astype(jnp.float32) / (N * k),
        "rows_routed": jnp.float32(N * k),
        **stats,
    }


def record_step_stats(stats: Dict[str, Any]) -> None:
    """A fetched :func:`routing_stats` (averaged over a call's blocks) into
    the metrics registry: counter ``moe/rows_routed``; gauges
    ``moe/experts_touched`` (the mean over the steps recorded since the
    registry was cleared), ``moe/max_load`` (the last step's) and, from a
    layer that holds a share of its experts, ``moe/rows_here_share`` (the
    mean, like the first)."""
    from trlx_tpu import telemetry

    registry = telemetry.get_metrics()
    registry.counter("moe/rows_routed").inc(float(stats["rows_routed"]))
    steps = registry.counter("moe/steps_recorded")
    steps.inc()
    for name in ("experts_touched", "rows_here_share", "skip_share"):
        if name not in stats:
            continue
        total = registry.counter(f"moe/{name}_sum")
        total.inc(float(stats[name]))
        if steps.value:  # 0 while the registry is disabled
            registry.gauge(f"moe/{name}").set(total.value / steps.value)
    registry.gauge("moe/max_load").set(float(stats["max_load"]))


def balance_losses(routing: Routing, num_experts: int, token_mask=None) -> Dict[str, jax.Array]:
    """``aux_loss``, ``router_z`` and ``max_load`` of one layer's routing
    (module docstring) over the tokens ``token_mask`` [N] marks (padding
    is routed like any row but balances nothing). ``f_e`` carries no
    gradient, ``P_e`` does."""
    N, k = routing.experts.shape
    m = jnp.ones((N,), jnp.float32) if token_mask is None else token_mask.reshape(N).astype(jnp.float32)
    n = jnp.maximum(jnp.sum(m), 1.0)
    counts = jnp.zeros((num_experts,), jnp.float32).at[routing.experts.reshape(-1)].add(
        jnp.repeat(m, k)
    )
    mean_prob = jnp.sum(routing.probs * m[:, None], axis=0) / n
    return {
        "aux_loss": num_experts * jnp.sum(jax.lax.stop_gradient(counts) / n * mean_prob),
        "router_z": jnp.sum(m * jax.nn.logsumexp(routing.logits, axis=-1) ** 2) / n,
        "max_load": jnp.max(counts) / (n * k),
    }


def expert_layer(h: jax.Array, router_w, w_gate, w_up, w_down, *, k: Optional[int] = None,
                 norm_topk: bool = False, dtype=jnp.bfloat16,
                 mesh: Optional[Mesh] = None, batch_axes=("dp", "fsdp"),
                 first_expert: int = 0, shared: Optional[jax.Array] = None,
                 routing: Optional[Routing] = None, activation: Optional[str] = None):
    """``h`` [B, T, D] -> ``(y [B, T, D] in dtype, routing)``; ``routing``
    is over all ``B * T`` tokens, for the losses and the statistics.

    The experts are gated (``w_gate``, ``w_up``, ``w_down``: SwiGLU) or
    plain: ``w_gate`` None and ``activation`` one of ``ACTIVATIONS``
    (``W_down act(W_up h)``; off a mesh only). Which form a traced call
    site took is counted in ``moe/expert_form{form=gated|plain}``.

    The router is ``router_w`` [D, E] with ``k`` and ``norm_topk``
    (:func:`route`), or the caller's own: ``router_w`` None and ``routing``
    a finished :class:`Routing` over the ``B * T`` tokens, ``E`` its width
    (off a mesh only; module docstring).

    ``mesh``: an ``ep`` mesh whose ``ep`` axis shards the experts' leading
    axis; tokens are split over ``batch_axes`` where their count allows
    and replicated otherwise (a decode step of a few rows). Without one,
    ``w_*`` are experts ``first_expert .. first_expert + w_gate.shape[0]``
    of the router's ``E`` (all of them, or one rank's share: module
    docstring). ``shared`` [B, T, D] float32 is added before the cast
    (off a mesh only)."""
    if (router_w is None) == (routing is None):
        raise ValueError("expert_layer takes router_w (with k) or a finished routing, not both or neither")
    plain = w_gate is None
    if plain != (activation is not None) or (plain and activation not in ACTIVATIONS):
        raise ValueError(
            f"expert_layer takes gated experts (w_gate, no activation) or plain ones (w_gate None and an "
            f"activation of {sorted(ACTIVATIONS)}); got w_gate {'None' if plain else 'given'}, "
            f"activation={activation!r}"
        )
    from trlx_tpu.telemetry import get_metrics

    get_metrics().counter("moe/expert_form{form=%s}" % ("plain" if plain else "gated")).inc()
    D = h.shape[-1]
    E = routing.probs.shape[-1] if router_w is None else router_w.shape[-1]

    def run(h_loc, router_w, w_gate, w_up, w_down, first_expert=0, routing=routing):
        flat = h_loc.reshape(-1, D)
        if routing is None:
            with jax.named_scope("moe_router"):
                routing = route(flat, router_w, k, norm_topk)
        return _apply_routed(flat, routing, w_gate, w_up, w_down, dtype, E, first_expert, activation), routing

    if mesh is None or dict(mesh.shape).get("ep", 1) == 1:
        if not 0 <= first_expert <= E - w_up.shape[0]:
            raise ValueError(
                f"experts {first_expert} .. {first_expert + w_up.shape[0]} "
                f"are not among the router's {E}"
            )
        y, routing = run(h, router_w, w_gate, w_up, w_down, first_expert)
        y = y.reshape(h.shape[:-1] + y.shape[-1:])
        if shared is not None:
            y = y + shared.astype(jnp.float32)
        return y.astype(dtype), routing

    ep = mesh.shape["ep"]
    if w_gate is None:
        raise ValueError("a plain expert (no gate) is not built on an ep mesh")
    if router_w is None:
        raise ValueError("a caller's own routing (a skip among its choices) is not built on an ep mesh")
    if E % ep or w_gate.shape[0] != E or first_expert:
        raise ValueError(
            f"an ep mesh shards all {E} experts over ep={ep}; got "
            f"{w_gate.shape[0]} from {first_expert} on"
        )
    if shared is not None:
        raise ValueError("a shared term beside the experts is not built on an ep mesh")
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    shards = 1
    for a in axes:
        shards *= mesh.shape[a]
    tok = P(axes) if axes and h.shape[0] % shards == 0 else P()

    def local(h_loc, *weights):
        part, routing = run(h_loc, *weights, first_expert=jax.lax.axis_index("ep") * (E // ep))
        return jax.lax.psum(part, "ep").reshape(h_loc.shape).astype(dtype), routing

    experts = P("ep")
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(tok, P(), experts, experts, experts),
        out_specs=(tok, Routing(tok, tok, tok, tok)),
        check_vma=False,
    )(h, router_w, w_gate, w_up, w_down)


# -- what the trainers read ------------------------------------------------- #


def moe_loss_summary(collection) -> Dict[str, jax.Array]:
    """Aggregate a ``moe_losses`` sow collection (one entry per MoE block)
    into scalars: mean ``aux_loss`` / ``router_z`` across layers, max
    ``max_load`` across layers. Used by trainers to add the balance
    penalty to the training loss and to surface routing health in stats."""
    buckets: Dict[str, list] = {"aux_loss": [], "router_z": [], "max_load": []}

    def walk(node):
        if isinstance(node, dict):
            for key, v in node.items():
                if key in buckets:
                    buckets[key].extend(v)  # sow stores a tuple per call
                else:
                    walk(v)

    walk(collection)
    if not buckets["aux_loss"]:
        raise ValueError("no MoE losses were sown — is this an MoE model?")
    return {
        "aux_loss": jnp.mean(jnp.stack(buckets["aux_loss"])),
        "router_z": jnp.mean(jnp.stack(buckets["router_z"])),
        "max_load": jnp.max(jnp.stack(buckets["max_load"])),
    }


def router_coefficients(cfg: Any):
    """(aux, z) coefficients of a family's config: gpt2_moe names them
    ``router_aux_coef`` / ``router_z_coef``, OLMoE publishes
    ``router_aux_loss_coef`` and has no z-loss (reported, coefficient 0)."""
    aux = getattr(cfg, "router_aux_coef", None)
    if aux is None:
        aux = getattr(cfg, "router_aux_loss_coef", 0.0)
    return aux, getattr(cfg, "router_z_coef", 0.0)


def apply_router_penalty(loss, stats, moe: Dict[str, jax.Array], cfg):
    """Add the router load-balancing penalty to a training loss and surface
    the routing health in the step stats — shared by every trainer that
    trains an MoE family (PPO and ILQL use identical objectives here)."""
    aux_coef, z_coef = router_coefficients(cfg)
    penalty = aux_coef * moe["aux_loss"] + z_coef * moe["router_z"]
    stats = dict(
        stats,
        **{
            "losses/total_loss": stats["losses/total_loss"] + penalty,
            "losses/moe_aux": moe["aux_loss"],
            "losses/router_z": moe["router_z"],
            "moe/max_load": moe["max_load"],
        },
    )
    return loss + penalty, stats
