"""Pipeline parallelism as pure GSPMD over a ``pipe`` mesh axis.

GPipe-schedule forward with stage-stacked activation buffers: activations
and token buffers carry an explicit leading *stage* axis of size ``pp``
that is sharding-constrained onto the ``pipe`` mesh axis; the microbatch
rotation is a ``jnp.roll`` along that axis, which GSPMD lowers to a
collective-permute between stage groups. Autodiff through the roll yields
the correct pipeline backward (the transposed permute). DP/TP compose
through ordinary GSPMD propagation on the other mesh axes — no manual
(shard_map) region is involved, so the step is a plain differentiable JAX
function.

The schedule is not a partially-manual ``shard_map`` over ``pipe``: the
pure-GSPMD formulation is equivalent math with simpler machinery, and it
differentiates like any other JAX function.

Stage layout: the stacked-periods axis of every block tensor is split
contiguously across stages (requires n_periods % pp == 0) — the same
geometry the Abstract Resource View assigns to the "pp" role, so PP
reconfiguration streams whole period-slices between stages (paper
App. A.2.3: "entire layers move; the intersection is the full tensor or
empty"). Embedding/head are pipe-replicated here (loss terms masked to
the owning stage); Megatron instead owns them on first/last stage — the
resource view models that ownership, the trainer trades the memory for
simplicity. MoE aux loss is not accumulated in the pipeline trainer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models import layers as L
from repro.models import model as M
from repro.models.transformer import _block_apply_full, block_program, n_periods
from repro.optim import AdamWConfig, adamw_update
from repro.utils.pytree import axes_paths, tree_paths, tree_from_paths


def pipeline_param_specs(cfg: ModelConfig, pp: int):
    """PartitionSpecs over the pipe axis (stacked-layer leaves only)."""
    from repro.models.model import abstract_params, param_logical_axes

    params = abstract_params(cfg)
    axes = axes_paths(param_logical_axes(cfg))
    flat = tree_paths(params)
    out = {}
    for path, leaf in flat.items():
        ax = axes[path]
        if ax and ax[0] == "layers":
            out[path] = P("pipe")
        else:
            out[path] = P()
    return tree_from_paths(out, params)


def _axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def make_pipeline_loss(
    cfg: ModelConfig,
    parallel: ParallelConfig,
    microbatches: int,
    mesh: Mesh,
):
    """Loss over a pipelined forward — an ordinary differentiable function
    (GSPMD handles all placement through sharding constraints)."""
    prog = block_program(cfg)
    np_ = n_periods(cfg)
    pp = parallel.pp
    assert np_ % pp == 0, f"n_periods {np_} must divide by pp {pp}"
    assert microbatches >= pp, "need microbatches >= pp to fill the pipeline"
    per_stage = np_ // pp
    dsz = _axis_size(mesh, "data")

    def buf_sharding(mb: int, extra_dims: int) -> NamedSharding:
        # (pp, mb, ...): stage axis on "pipe"; microbatch on "data" when it
        # divides, else replicated over data
        bspec = "data" if dsz > 1 and mb % dsz == 0 else None
        return NamedSharding(mesh, P("pipe", bspec, *([None] * extra_dims)))

    def pipe_loss(params, tokens):
        Bl, S = tokens.shape
        assert Bl % microbatches == 0, (Bl, microbatches)
        mb = Bl // microbatches
        toks = tokens.reshape(microbatches, mb, S)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mb, S))
        adt = jnp.dtype(cfg.dtype)
        d = cfg.d_model
        T = microbatches + pp - 1
        x_sh = buf_sharding(mb, 2)
        tok_sh = buf_sharding(mb, 1)

        # stage-stack the block tensors: (np_, ...) -> (pp, per_stage, ...)
        stage_blocks = jax.tree_util.tree_map(
            lambda a: lax.with_sharding_constraint(
                a.reshape((pp, per_stage) + a.shape[1:]),
                NamedSharding(mesh, P("pipe")),
            ),
            params["blocks"],
        )
        stage_idx = jnp.arange(pp)

        def stage_forward(blocks, x):
            """One stage's periods over one microbatch (vmapped over pp)."""

            def body(carry, period_params):
                h = carry
                for j, (mixer, mlp) in enumerate(prog):
                    h, _, _ = _block_apply_full(
                        period_params[f"pos{j}"], cfg, mixer, mlp, h, positions, True
                    )
                return h, None

            x, _ = lax.scan(jax.checkpoint(body), x, blocks)
            return x

        vfwd = jax.vmap(stage_forward)

        def tick(carry, t):
            x_buf, tok_buf, loss_acc = carry
            inject_idx = jnp.clip(t, 0, microbatches - 1)
            tok_inject = toks[inject_idx]
            x_inject = L.embed_apply(params["embed"], tok_inject, adt)
            use_inject = t < microbatches
            x_in = x_buf.at[0].set(jnp.where(use_inject, x_inject, x_buf[0]))
            tok_in = tok_buf.at[0].set(
                jnp.where(use_inject, tok_inject, tok_buf[0])
            )
            x_in = lax.with_sharding_constraint(x_in, x_sh)

            y = vfwd(stage_blocks, x_in)  # (pp, mb, S, d)
            y = lax.with_sharding_constraint(y, x_sh)

            # per-stage CE, masked to the last stage in steady state. The
            # head matmul runs per stage slice (one per pipe group — the
            # same unconditional-compute-then-mask pattern a lax.cond would
            # break by hiding the TP collective from non-last stages.
            h = L.rmsnorm_apply(params["final_norm"], y)
            logits = L.lm_head_apply(
                params.get("lm_head"), params["embed"], h
            ).astype(jnp.float32)
            lz = jax.scipy.special.logsumexp(logits[:, :, :-1], axis=-1)
            tgt = jnp.take_along_axis(
                logits[:, :, :-1], tok_in[:, :, 1:, None], axis=-1
            )[..., 0]
            stage_loss = (lz - tgt).mean(axis=(1, 2))  # (pp,)
            is_out = (stage_idx == pp - 1) & (t >= pp - 1)
            loss_acc = loss_acc + jnp.sum(jnp.where(is_out, stage_loss, 0.0))

            # rotate: stage s's output becomes stage s+1's input (GSPMD
            # lowers the roll on the pipe-sharded axis to collective-permute)
            x_send = lax.with_sharding_constraint(jnp.roll(y, 1, axis=0), x_sh)
            tok_send = lax.with_sharding_constraint(
                jnp.roll(tok_in, 1, axis=0), tok_sh
            )
            return (x_send, tok_send, loss_acc), None

        x0 = lax.with_sharding_constraint(jnp.zeros((pp, mb, S, d), adt), x_sh)
        tok0 = lax.with_sharding_constraint(
            jnp.zeros((pp, mb, S), jnp.int32), tok_sh
        )
        (xf, tokf, loss_sum), _ = lax.scan(
            tick, (x0, tok0, jnp.float32(0.0)), jnp.arange(T)
        )
        return loss_sum / microbatches

    return pipe_loss


def merged_pipeline_shardings(cfg: ModelConfig, mesh: Mesh, parallel: ParallelConfig):
    """Device shardings for pipelined params: pipe on the stacked axis of
    block tensors, model/data axes via the standard rules."""
    from repro.distribution.sharding import param_shardings
    from repro.models.model import abstract_params

    pipe_specs = pipeline_param_specs(cfg, parallel.pp)
    ps_rules = param_shardings(cfg, mesh)

    def merge(rule_sh, pipe_spec, leaf):
        spec = list(rule_sh.spec) + [None] * (leaf.ndim - len(rule_sh.spec))
        if pipe_spec and len(pipe_spec) > 0 and pipe_spec[0] == "pipe":
            spec[0] = "pipe"
        while spec and spec[-1] is None:
            spec.pop()
        return NamedSharding(mesh, P(*spec))

    aparams = abstract_params(cfg)
    return jax.tree_util.tree_map(merge, ps_rules, pipe_specs, aparams)


def jit_pipeline_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    parallel: ParallelConfig,
    opt_cfg: AdamWConfig,
    global_batch: int,
    microbatches: int,
):
    """Pipelined pjit train step on an elastic mesh with a 'pipe' axis.

    Returns (jitted_fn(params, opt_state, batch)->(params,opt,metrics),
    (param_shardings, opt_shardings, batch_shardings)).
    """
    pipe_loss = make_pipeline_loss(cfg, parallel, microbatches, mesh)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: pipe_loss(p, batch["tokens"])
        )(params)
        new_params, new_opt, om = adamw_update(opt_cfg, grads, opt_state, params)
        return new_params, new_opt, {"loss": loss, **om}

    from repro.distribution.sharding import batch_sharding

    ps = merged_pipeline_shardings(cfg, mesh, parallel)
    os_ = {"mu": ps, "nu": ps, "count": NamedSharding(mesh, P())}
    bs = {"tokens": batch_sharding(mesh, global_batch)}
    from repro.kernels import ops

    jitted = jax.jit(
        ops.with_kernel_mesh(train_step, mesh),
        in_shardings=(ps, os_, bs),
        out_shardings=(ps, os_, None),
        donate_argnums=(0, 1),
    )
    return jitted, (ps, os_, bs)
