"""Pipeline parallelism over the ``pod`` axis, planned by AMTHA.

The paper's algorithm assigns layer blocks to pods
(`repro.core.placement.assign_layers_to_pods`: tasks = layer blocks,
comm edges = activation volumes, DCI = the slow level); this module
*executes* that assignment as a GPipe-style pipeline:

* stage parameters are stacked on a leading (n_stages,) dim sharded over
  ``pod`` — each pod holds only its stage's layers;
* microbatches advance one stage per tick; activations hop pods via
  ``collective_permute``; the schedule runs n_micro + n_stages − 1 ticks
  (bubble fraction (S−1)/(T+S−1));
* the tick loop is a ``lax.scan``, so the whole pipeline is
  differentiable (grad flows backward through ppermute) — the train
  demo takes real gradients through the pipeline.

Scope: composes with data parallelism inside each stage (the shard_map
is manual over every mesh axis; the stage body is local compute). The
PP×TP composition (partial-manual shard_map with a live `model` axis
inside the stage) is left documented — the dry-run meshes use the pod
axis for DP instead (DESIGN.md §8).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def plan_stages(n_layers: int, n_pods: int, layer_flops: float,
                act_bytes: float, *, pod_speed_flops: float | None = None,
                link_bandwidth: float | None = None,
                link_latency: float = 1e-5):
    """AMTHA stage plan for homogeneous pods. Returns layers-per-stage
    and the assignment; validates that AMTHA's chain mapping is (as
    expected for a single chain on equal pods) contiguous — the
    executable layout requires equal contiguous stages.

    The balance objective is comm-aware: the per-microbatch stage tick
    time ``sa.t_stage`` charges the inter-stage activation hop
    (``link_latency + act_bytes / link_bandwidth``, the slow inter-pod
    level by default) on top of the compute term, so the heuristic's
    predicted pipeline time ``(n_micro + S - 1) * t_stage`` is honest
    about what each extra stage costs. What this heuristic still cannot
    see — which *device* each stage lands on, i.e. whether consecutive
    stages pay an ICI hop or a DCN hop on a hierarchical machine, and
    co-locating stages when comm dominates — is exactly the gap
    ``repro.autoplace`` closes by searching the placement.
    """
    from repro.core.machine import TPU_V5E_DCI_BW, TPU_V5E_PEAK_FLOPS
    from repro.core.placement import assign_layers_to_pods
    assert n_layers % n_pods == 0, "equal stages required for the layout"
    speed = pod_speed_flops if pod_speed_flops is not None \
        else TPU_V5E_PEAK_FLOPS * 256
    bw = link_bandwidth if link_bandwidth is not None else TPU_V5E_DCI_BW
    sa = assign_layers_to_pods([layer_flops] * n_layers,
                               [act_bytes] * (n_layers - 1),
                               [speed] * n_pods)
    per = n_layers // n_pods
    sa.comm_time = (link_latency + act_bytes / bw) if n_pods > 1 else 0.0
    sa.t_stage = per * layer_flops / speed + sa.comm_time
    return per, sa


def predicted_pipeline_time(t_stage: float, n_stages: int,
                            n_micro: int) -> float:
    """GPipe fill-drain schedule length for a balanced plan: the pipeline
    runs ``n_micro + n_stages - 1`` ticks of the bottleneck stage time."""
    return (n_micro + n_stages - 1) * t_stage


def gpipe(stage_fn, stage_params, x_micro, *, pod_axis: str, mesh,
          in_spec=P(None, None, None)):
    """Run the pipeline. ``stage_params``: pytree with leading
    (n_stages,) dim; ``x_micro``: (n_micro, B_m, S, d) embedded inputs.
    ``stage_fn(params_local, x) -> x`` applies one stage (its layer
    slice). Returns (n_micro, B_m, S, d) after every stage."""
    n_micro = x_micro.shape[0]

    def body(params_stage, xm):
        # params_stage keeps a leading dim of size 1 under shard_map
        params_loc = jax.tree.map(lambda t: t[0], params_stage)
        p = jax.lax.axis_index(pod_axis)
        n_pods = jax.lax.psum(1, pod_axis)
        total = n_micro + n_pods - 1
        buf = jax.lax.pvary(jnp.zeros_like(xm[0]), (pod_axis,))
        out0 = jax.lax.pvary(jnp.zeros_like(xm), (pod_axis,))
        perm = [(i, i + 1) for i in range(n_pods - 1)]

        def tick(carry, t):
            buf, out = carry
            mb = t - p
            valid = (mb >= 0) & (mb < n_micro)
            mb_c = jnp.clip(mb, 0, n_micro - 1)
            inp = jnp.where(p == 0, xm[mb_c], buf)
            y = stage_fn(params_loc, inp)
            y = jnp.where(valid, y, buf)
            is_last = p == n_pods - 1
            out = out.at[mb_c].set(
                jnp.where(valid & is_last, y, out[mb_c]))
            buf = jax.lax.ppermute(y, pod_axis, perm)
            return (buf, out), None

        (buf, out), _ = jax.lax.scan(tick, (buf, out0),
                                     jnp.arange(total))
        # output lives on the last pod; replicate it across the pipeline
        out = jax.lax.psum(
            jnp.where(p == n_pods - 1, out, jnp.zeros_like(out)), pod_axis)
        return out

    n_stages = jax.tree.leaves(stage_params)[0].shape[0]
    pspec = jax.tree.map(
        lambda t: P(pod_axis, *([None] * (t.ndim - 1))), stage_params)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P(None, *in_spec)),
        out_specs=P(None, *in_spec))(stage_params, x_micro)


def restack_for_stages(group_params, n_stages: int):
    """(n_rep, ...) stacked scan params -> (n_stages, n_rep/n_stages, ...)
    — the executable form of AMTHA's contiguous equal stage plan."""
    def re(t):
        n_rep = t.shape[0]
        assert n_rep % n_stages == 0
        return t.reshape(n_stages, n_rep // n_stages, *t.shape[1:])
    return jax.tree.map(re, group_params)


def make_pipelined_forward(cfg, mesh, n_stages: int, pod_axis: str = "pod"):
    """Pipelined LM forward for repeat-only archs (prologue/tail-free):
    embed (replicated) -> staged blocks over pods -> head. The repeat
    unit may hold several layer kinds (gemma2's local/global pair): the
    stage scans whole units, applying each kind in order, so any
    ``n_stages`` dividing ``n_rep`` is executable. Returns
    fn(params, tokens (n_micro, B_m, S)) -> logits (n_micro, B_m, S, V)."""
    from repro.models.blocks import layer_forward
    from repro.models.model import ShardCtx, _embed, _head
    prologue, n_rep, unit, tail = cfg.repeat_structure()
    assert not prologue and not tail and not cfg.shared_attn_every, \
        "pipelined path supports repeat-only archs"
    ctx = ShardCtx(mode="train", vma_axes=(pod_axis,))

    def stage_fn(params_loc, x):
        def one(x, gp):
            for pos, kind in enumerate(unit):
                x, _, _ = layer_forward(kind, gp[str(pos)], x, cfg=cfg,
                                        ctx=ctx,
                                        positions=jnp.arange(x.shape[1]))
            return x, None
        y, _ = jax.lax.scan(one, x, params_loc)
        return y

    def fwd(params, tokens_micro):
        n_micro, bm, s = tokens_micro.shape
        emb = jax.vmap(lambda t: _embed(params, {"tokens": t}, cfg)[0]
                       )(tokens_micro)
        stages = restack_for_stages(params["groups"], n_stages)
        y = gpipe(stage_fn, stages, emb, pod_axis=pod_axis, mesh=mesh)
        return jax.vmap(lambda h: _head(params, h, cfg))(y)

    return fwd
