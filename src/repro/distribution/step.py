"""pjit step builders: train (with gradient accumulation and optional int8
error-feedback gradient compression), prefill, decode.

``make_*`` return pure functions; ``jit_*`` wrap them with shardings for a
mesh — the shadow world lowers/compiles these against the *target* mesh while
the active world keeps stepping (paper §4.4).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, TrainConfig
from repro.kernels import ops, reshard_quant
from repro.distribution.sharding import (
    batch_sharding,
    cache_shardings,
    opt_state_shardings,
    param_shardings,
)
from repro.models import model as M
from repro.optim import AdamWConfig, adamw_init, adamw_update


def make_grad_fn(
    cfg: ModelConfig,
    microbatches: int = 1,
    remat: str = "full",
    grad_accum: str = "explicit",
):
    """Returns grad_step(params, batch) -> (loss, metrics, grads) — the
    forward/backward half of the train step, shared verbatim by
    ``make_train_step`` and the split-step commit path (overlapped live
    reconfiguration streams state while this runs on the old world, then
    applies ``make_update_fn`` on the new one)."""

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, batch, remat=remat), has_aux=True
        )(params)
        return loss, metrics, grads

    def grad_step(params, batch):
        tokens = batch["tokens"]
        if microbatches > 1 and grad_accum == "scan_loss":
            import os as _os

            b = tokens.shape[0]
            assert b % microbatches == 0
            mb = b // microbatches

            def scan_loss(p):
                def mk_micro(i):
                    sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)
                    return jax.tree_util.tree_map(sl, batch)

                @jax.checkpoint
                def body(acc, i):
                    l, _ = M.loss_fn(cfg, p, mk_micro(i), remat=remat)
                    return acc + l, None

                total, _ = jax.lax.scan(
                    body,
                    jnp.float32(0.0),
                    jnp.arange(microbatches),
                    unroll=_os.environ.get("REPRO_SCAN_UNROLL") == "1",
                )
                return total / microbatches

            loss, grads = jax.value_and_grad(scan_loss)(params)
            metrics = {}
        elif microbatches > 1:
            b = tokens.shape[0]
            assert b % microbatches == 0
            mb = b // microbatches

            def mk_micro(i):
                sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)
                return jax.tree_util.tree_map(sl, batch)

            def accum(carry, i):
                g_acc, loss_acc = carry
                loss, _, grads = grads_of(params, mk_micro(i))
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, grads
                )
                return (g_acc, loss_acc + loss), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            import os as _os

            (g_sum, loss_sum), _ = jax.lax.scan(
                accum,
                (zeros, 0.0),
                jnp.arange(microbatches),
                unroll=_os.environ.get("REPRO_SCAN_UNROLL") == "1",
            )
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, g_sum)
            loss = loss_sum / microbatches
            metrics = {}
        else:
            loss, metrics, grads = grads_of(params, batch)
        return loss, metrics, grads

    return grad_step


def make_update_fn(opt_cfg: AdamWConfig, compression: str = "none"):
    """Returns update(grads, opt_state, params) -> (params, opt, metrics) —
    the optimizer half of the train step (elementwise up to the grad-clip
    global norm, so it can run on a different sharding than the gradients
    were computed under)."""

    def update(grads, opt_state, params):
        if compression == "int8_ef":
            grads, opt_state = reshard_quant.compress_decompress_with_ef(
                grads, opt_state
            )
        return adamw_update(opt_cfg, grads, opt_state, params)

    return update


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: AdamWConfig,
    microbatches: int = 1,
    remat: str = "full",
    compression: str = "none",
    hints: dict | None = None,
    grad_accum: str = "explicit",
):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    ``grad_accum``: "explicit" computes per-microbatch gradients and sums
    them (baseline; XLA emits the gradient collectives inside the loop —
    one reduction PER MICROBATCH); "scan_loss" differentiates through a
    rematted scan over microbatches, so gradient collectives are emitted
    once per step (§Perf iteration: M microbatches → ~M× less gradient
    reduction traffic; same math, same rematerialized memory profile).

    ``hints``: activation-sharding constraints (models.shard_hints), applied
    at trace time — the §Perf hillclimbing mechanism; None = paper-faithful
    baseline (pure GSPMD propagation).
    """

    from repro.models import shard_hints

    grad_step = make_grad_fn(cfg, microbatches, remat, grad_accum)
    update = make_update_fn(opt_cfg, compression)

    def train_step(params, opt_state, batch):
        loss, metrics, grads = grad_step(params, batch)
        new_params, new_opt, opt_metrics = update(grads, opt_state, params)
        out_metrics = {"loss": loss, **metrics, **opt_metrics}
        return new_params, new_opt, out_metrics

    def train_step_hinted(params, opt_state, batch):
        with shard_hints.active(hints):
            return train_step(params, opt_state, batch)

    return train_step_hinted if hints else train_step


def make_prefill_step(cfg: ModelConfig, max_seq: int = 0, hints: dict | None = None):
    from repro.models import shard_hints

    def prefill_step(params, batch):
        with shard_hints.active(hints):
            logits, cache, cross_kv = M.prefill(cfg, params, batch, max_seq=max_seq)
        if cross_kv is None:
            return logits, cache
        return logits, cache, cross_kv

    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, cache, tokens, pos, cross_kv=None):
        if cfg.family == "encdec":
            return M.decode_step(cfg, params, cache, tokens, pos, cross_kv)
        return M.decode_step(cfg, params, cache, tokens, pos)

    return decode_step


# ---------------------------------------------------------------------------
# Sharded wrappers
# ---------------------------------------------------------------------------


def train_state_shardings(cfg: ModelConfig, mesh: Mesh):
    return param_shardings(cfg, mesh), opt_state_shardings(cfg, mesh)


def jit_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    opt_cfg: AdamWConfig,
    global_batch: int,
    microbatches: int = 1,
    remat: str = "full",
    compression: str = "none",
    hint_version: str | None = None,
    grad_accum: str = "explicit",
):
    """Returns (jitted_fn, (param_sh, opt_sh, batch_sh))."""
    hints = None
    if hint_version:
        from repro.models.shard_hints import make_train_hints

        hints = make_train_hints(mesh, hint_version)
    ps, os_ = train_state_shardings(cfg, mesh)
    if compression == "int8_ef":
        os_ = dict(os_)
        os_["ef"] = ps  # error-feedback buffers mirror params
    bs = batch_sharding(mesh, global_batch)
    batch_sh = {"tokens": bs}
    if cfg.family == "encdec":
        batch_sh["frames"] = bs
    fn = make_train_step(cfg, opt_cfg, microbatches, remat, compression,
                         hints=hints, grad_accum=grad_accum)
    jitted = jax.jit(
        ops.with_kernel_mesh(fn, mesh),
        in_shardings=(ps, os_, batch_sh),
        out_shardings=(ps, os_, None),
        donate_argnums=(0, 1),
    )
    return jitted, (ps, os_, batch_sh)


def jit_grad_step(
    cfg: ModelConfig,
    mesh: Mesh,
    global_batch: int,
    microbatches: int = 1,
    remat: str = "full",
    hint_version: str | None = None,
    grad_accum: str = "explicit",
    parallel=None,
):
    """Grads-only step for the split-step commit: (params, batch) ->
    (loss, grads). Params are NOT donated — the overlapped resharder
    streams them concurrently with this computation."""
    from repro.models import shard_hints

    hints = None
    if hint_version:
        from repro.models.shard_hints import make_train_hints

        hints = make_train_hints(mesh, hint_version)
    ps = param_shardings(cfg, mesh)
    bs = batch_sharding(mesh, global_batch)
    batch_sh = {"tokens": bs}
    if cfg.family == "encdec":
        batch_sh["frames"] = bs
    if parallel is not None and parallel.pp > 1:
        from repro.distribution.pipeline import (
            make_pipeline_loss,
            merged_pipeline_shardings,
        )

        loss_fn = make_pipeline_loss(
            cfg, parallel, max(microbatches, parallel.pp), mesh
        )
        ps = merged_pipeline_shardings(cfg, mesh, parallel)

        def fn(params, batch):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch["tokens"])
            )(params)
            return loss, grads

    else:
        grad_step = make_grad_fn(cfg, microbatches, remat, grad_accum)

        def fn(params, batch):
            with shard_hints.active(hints):
                loss, _, grads = grad_step(params, batch)
            return loss, grads

    jitted = jax.jit(
        ops.with_kernel_mesh(fn, mesh),
        in_shardings=(ps, batch_sh),
        out_shardings=(None, ps),
    )
    return jitted, (ps, batch_sh)


def jit_update_step(
    cfg: ModelConfig,
    mesh: Mesh,
    opt_cfg: AdamWConfig,
    compression: str = "none",
    parallel=None,
):
    """Optimizer-only step for the split-step commit, compiled for the NEW
    world: (grads, opt_state, params) -> (params, opt, metrics). Grads,
    state and params all arrive in the new world's shardings; params and
    opt are donated (they are the freshly streamed copies)."""
    if parallel is not None and parallel.pp > 1:
        from repro.distribution.pipeline import merged_pipeline_shardings

        ps = merged_pipeline_shardings(cfg, mesh, parallel)
        os_ = {"mu": ps, "nu": ps, "count": NamedSharding(mesh, P())}
    else:
        ps, os_ = train_state_shardings(cfg, mesh)
        if compression == "int8_ef":
            os_ = dict(os_)
            os_["ef"] = ps
    fn = make_update_fn(opt_cfg, compression)
    jitted = jax.jit(
        fn,
        in_shardings=(ps, os_, ps),
        out_shardings=(ps, os_, None),
        donate_argnums=(1, 2),
    )
    return jitted, (ps, os_)


def jit_prefill_step(
    cfg: ModelConfig, mesh: Mesh, global_batch: int, seq_len: int,
    hint_version: str | None = None,
):
    hints = None
    if hint_version:
        from repro.models.shard_hints import make_train_hints

        hints = make_train_hints(mesh, hint_version)
    ps = param_shardings(cfg, mesh)
    bs = batch_sharding(mesh, global_batch)
    batch_sh = {"tokens": bs}
    if cfg.family == "encdec":
        batch_sh["frames"] = bs
    fn = ops.with_kernel_mesh(make_prefill_step(cfg, max_seq=seq_len, hints=hints), mesh)
    return jax.jit(fn, in_shardings=(ps, batch_sh)), (ps, batch_sh)


def jit_decode_step(
    cfg: ModelConfig, mesh: Mesh, global_batch: int, max_seq: int,
    serve_params: str = "fsdp",
):
    """serve_params: "fsdp" shards params over (data, model) like training
    (baseline — pays a param all-gather every decode step); "replicated"
    shards over model only, replicating across data (the serving-optimized
    layout, §Perf iteration)."""
    ps = param_shardings(cfg, mesh, serving=(serve_params == "replicated"))
    cs = cache_shardings(cfg, mesh, global_batch, max_seq)
    bs = batch_sharding(mesh, global_batch)
    rep = NamedSharding(mesh, P())
    fn = make_decode_step(cfg)
    in_sh = [ps, cs, bs, rep]
    if cfg.family == "encdec":
        from repro.models import kvcache

        xsh = jax.eval_shape(
            lambda: kvcache.init_cross_kv(cfg, global_batch, min(max_seq, 4096))
        )
        cross_sh = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), xsh
        )
        in_sh.append(cross_sh)
    jitted = jax.jit(
        fn,
        in_shardings=tuple(in_sh),
        out_shardings=(None, cs),
        donate_argnums=(1,),
    )
    return jitted, tuple(in_sh)


def init_train_state(
    cfg: ModelConfig, mesh: Mesh, seed: int = 0, compression: str = "none"
):
    """Initialize (params, opt_state) directly sharded on the mesh."""
    ps, os_ = train_state_shardings(cfg, mesh)

    def init(rng):
        params = M.init_params(cfg, rng)
        opt = adamw_init(params)
        return params, opt

    out_sh = (ps, os_)
    if compression == "int8_ef":
        def init(rng):  # noqa: F811
            params = M.init_params(cfg, rng)
            opt = adamw_init(params)
            opt["ef"] = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            return params, opt

        os2 = dict(os_)
        os2["ef"] = ps
        out_sh = (ps, os2)
    rng = jax.random.key(seed)
    return jax.jit(init, out_shardings=out_sh)(rng)
