"""pjit step builders: train_step / prefill_step / decode_step per RunConfig.

This is the distribution heart of the framework: logical PartitionSpecs from
the model zoo become NamedShardings on the production mesh, and the steps
are ``jax.jit``s with explicit in/out shardings and donated state.

Train step structure:
  microbatch scan (gradient accumulation)  ->  grads
  [optional] EF-int8 gradient compression hook (cross-pod trick)
  global-norm clip + AdamW/Adafactor update (donated params/opt state)

Serve steps:
  prefill: full causal pass -> (last logits, KV cache)
  decode:  one token against the cache (``write=False`` for cells whose
           cache is at capacity; the serve loop uses write=True)
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import RunConfig
from repro.models import build_model, input_specs
from repro.optim import compression
from repro.optim.optimizer import opt_init, opt_update, spec_for_state

F32 = jnp.float32


def _named(mesh: Mesh, spec_tree):
    """PartitionSpec pytree -> NamedSharding pytree.

    Empty-tuple subtrees (e.g. MLA's ``KVCache.v = ()``) stay empty so the
    jit sharding pytree keeps the argument's structure.
    """
    def conv(s):
        if isinstance(s, tuple) and not isinstance(s, P) and len(s) == 0:
            return ()
        if s is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, s)
    return jax.tree_util.tree_map(
        conv, spec_tree,
        is_leaf=lambda x: isinstance(x, P) or x is None
        or (isinstance(x, tuple) and len(x) == 0))


def _filter_axes(spec_tree, mesh: Mesh):
    """Drop mesh axes a spec references but the mesh doesn't have (e.g.
    'pod' on the single-pod mesh)."""
    names = set(mesh.axis_names)

    def fix_entry(e):
        if e is None:
            return None
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a in names)
            return kept if kept else None
        return e if e in names else None

    def fix(s):
        if not isinstance(s, P):
            return s
        return P(*(fix_entry(e) for e in s))

    return jax.tree_util.tree_map(
        fix, spec_tree, is_leaf=lambda x: isinstance(x, P))


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def _fix_divisibility(spec_tree, struct_tree, mesh: Mesh):
    """Repair specs whose dim sizes aren't divisible by their mesh axes.

    jit *argument* shardings must divide evenly. Where they don't (GQA
    kv=8 heads over a 16-way model axis, batch-1 long-context cells, grok's
    8 experts), relocate the axis to the rightmost unsharded divisible dim
    (e.g. kv-head axis -> head_dim) or, failing that, drop it (replicate).
    Deterministic, so every arg/out spec pair fixes identically.
    """
    def fix(spec, sds):
        if not isinstance(spec, P) or not hasattr(sds, "shape"):
            return spec
        shape = sds.shape
        entries = list(spec) + [None] * (len(shape) - len(spec))
        entries = entries[:len(shape)]
        for i, e in enumerate(entries):
            if e is None:
                continue
            size = _axis_size(mesh, e)
            if size > 1 and shape[i] % size != 0:
                moved = False
                for j in range(len(shape) - 1, -1, -1):
                    if j != i and entries[j] is None \
                            and shape[j] % size == 0 and shape[j] > 1:
                        entries[j] = e
                        moved = True
                        break
                entries[i] = None
        return P(*entries)

    return jax.tree_util.tree_map(
        fix, spec_tree, struct_tree, is_leaf=lambda x: isinstance(x, P))


def _apply_fsdp(pspecs, struct_tree, mesh: Mesh, *, axis: str = "data"):
    """FSDP/ZeRO-3: shard a *feature* dim of stacked-layer weights over
    ``axis``, so the per-iteration ``lax.scan`` slice stays sharded and
    GSPMD all-gathers one layer's weights just-in-time inside the loop.

    Never shards dim 0 (the scan axis): GSPMD lowers a dynamic-slice over
    a sharded dim as gather-then-slice, which LICM hoists out of the loop
    — the whole f32 weight stack materializes per device (observed 28 ×
    24 GiB buffers on the grok-1 train cell with scan-dim FSDP).
    """
    if axis not in mesh.axis_names:
        return pspecs
    size = mesh.shape[axis]
    leafP = lambda x: isinstance(x, P)
    flat = jax.tree_util.tree_flatten_with_path(pspecs, is_leaf=leafP)[0]
    treedef = jax.tree_util.tree_structure(pspecs, is_leaf=leafP)
    structs = jax.tree_util.tree_flatten_with_path(
        struct_tree, is_leaf=lambda x: hasattr(x, "shape"))[0]
    shapes = {tuple(str(p) for p in path): s.shape for path, s in structs}
    out = []
    for path, spec in flat:
        key = tuple(str(p) for p in path)
        keys_str = "/".join(key)
        shape = shapes.get(key)
        stacked = "layers" in keys_str
        if stacked and isinstance(spec, P) and shape is not None \
                and len(shape) >= 3:
            entries = list(spec) + [None] * (len(shape) - len(spec))
            for i in range(1, len(shape)):      # skip the scan axis
                if entries[i] is None and shape[i] % size == 0 \
                        and shape[i] >= size:
                    entries[i] = axis
                    break
            spec = P(*entries)
        out.append(spec)
    return jax.tree_util.tree_unflatten(treedef, out)


class TrainStep(NamedTuple):
    step: Callable            # (params, opt_state, ef, batch) -> (...)
    init_state: Callable      # (rng) -> (params, opt_state, ef)
    model: Any
    param_shardings: Any
    opt_shardings: Any
    batch_shardings: Any
    input_structs: Dict[str, jax.ShapeDtypeStruct]


def make_train_step(run: RunConfig, mesh: Mesh) -> TrainStep:
    model = build_model(run.model, remat=run.remat)
    structs, batch_pspecs = input_specs(run.model, run.shape)
    pspecs = _filter_axes(model.param_specs(), mesh)
    batch_pspecs = _filter_axes(batch_pspecs, mesh)

    n_micro = run.microbatches
    if n_micro > 1:
        # microbatch axis leads; the data axes shard dim 1
        structs = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                (n_micro, s.shape[0] // n_micro) + s.shape[1:], s.dtype),
            structs)
        batch_pspecs = jax.tree_util.tree_map(
            lambda p: P(None, *p), batch_pspecs,
            is_leaf=lambda x: isinstance(x, P))

    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    if run.fsdp:
        pspecs = _apply_fsdp(pspecs, params_shape, mesh)
    pspecs = _fix_divisibility(pspecs, params_shape, mesh)
    batch_pspecs = _fix_divisibility(batch_pspecs, structs, mesh)
    opt_struct = jax.eval_shape(
        functools.partial(opt_init, run.optimizer), params_shape)
    opt_pspecs = _fix_divisibility(
        _filter_axes(spec_for_state(run.optimizer, pspecs, params_shape),
                     mesh), opt_struct, mesh)

    param_sh = _named(mesh, pspecs)
    opt_sh = _named(mesh, opt_pspecs)
    batch_sh = _named(mesh, batch_pspecs)
    compress = run.optimizer.compress_grads

    def loss_fn(params, batch):
        loss, _ = model.loss(params, batch["tokens"],
                             **{k: v for k, v in batch.items()
                                if k != "tokens"})
        return loss

    spec_leaves = jax.tree_util.tree_flatten(
        pspecs, is_leaf=lambda x: isinstance(x, P))[0]

    def constrain_like_params(tree):
        """Pin a params-shaped tree (grads, accumulators) to the param
        PartitionSpecs. Without this GSPMD de-shards the FSDP (layer)
        dim of grad accumulators for scanned stacks — observed as
        24 GiB f32 full-stack gradient buffers on the grok-1 train cell."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        assert len(leaves) == len(spec_leaves)
        out = [jax.lax.with_sharding_constraint(x, s)
               if isinstance(s, P) else x
               for x, s in zip(leaves, spec_leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    def grads_of(params, batch):
        if n_micro == 1:
            loss, g = jax.value_and_grad(loss_fn)(params, batch)
            return loss, constrain_like_params(g)
        # gradient accumulation: batch arrives pre-split [micro, B/micro,...]
        # (splitting must happen OUTSIDE jit: an in-graph reshape of the
        # batch-sharded dim makes GSPMD partially replicate the whole step —
        # observed 4× flop inflation on the granite cell before this).
        def micro(carry, mb):
            loss_acc, grad_acc = carry
            l, g = jax.value_and_grad(loss_fn)(params, mb)
            acc = jax.tree_util.tree_map(
                lambda a, b: a + b / n_micro, grad_acc, g)
            return (loss_acc + l / n_micro, constrain_like_params(acc)), ()

        zero = constrain_like_params(jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, F32), params))
        (loss, grads), _ = jax.lax.scan(micro, (jnp.zeros((), F32), zero),
                                        batch)
        return loss, grads

    def step(params, opt_state, ef, batch):
        loss, grads = grads_of(params, batch)
        if compress:
            # EF-int8 hook: quantize/dequantize with error feedback — the
            # numerical twin of the cross-pod compressed all-reduce (the
            # collective itself is GSPMD's; bytes accounting in §Roofline).
            q, s, ef = compression.compress_with_feedback(grads, ef)
            grads = jax.tree_util.tree_map(compression.dequantize, q, s)
        params, opt_state, om = opt_update(run.optimizer, grads, opt_state,
                                           params)
        metrics = {"loss": loss, **om}
        return params, opt_state, ef, metrics

    ef_sh = param_sh if compress else None

    jit_step = jax.jit(
        step,
        in_shardings=(param_sh, opt_sh, ef_sh, batch_sh),
        out_shardings=(param_sh, opt_sh, ef_sh,
                       _named(mesh, {"loss": P(), "lr": P(),
                                     "grad_norm": P()})),
        donate_argnums=(0, 1, 2),
    )

    def init_state(rng):
        params = jax.jit(model.init_params, out_shardings=param_sh)(rng)
        opt_state = jax.jit(
            functools.partial(opt_init, run.optimizer),
            out_shardings=opt_sh)(params)
        ef = (jax.jit(compression.ef_init, out_shardings=ef_sh)(params)
              if compress else None)
        return params, opt_state, ef

    return TrainStep(step=jit_step, init_state=init_state, model=model,
                     param_shardings=param_sh, opt_shardings=opt_sh,
                     batch_shardings=batch_sh, input_structs=structs)


class ServeStep(NamedTuple):
    prefill: Callable
    decode: Callable
    model: Any
    param_shardings: Any
    cache_shardings: Any
    input_structs: Dict[str, jax.ShapeDtypeStruct]


def make_serve_step(run: RunConfig, mesh: Mesh, *,
                    decode_write: bool = False) -> ServeStep:
    model = build_model(run.model, remat="none")
    structs, batch_pspecs = input_specs(run.model, run.shape)
    pspecs = _filter_axes(model.param_specs(), mesh)
    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    if run.fsdp:
        pspecs = _apply_fsdp(pspecs, params_shape, mesh)
    pspecs = _fix_divisibility(pspecs, params_shape, mesh)
    cache_struct = jax.eval_shape(functools.partial(
        model.init_cache, run.shape.global_batch, run.shape.seq_len))
    cache_pspecs = _fix_divisibility(
        _filter_axes(model.cache_specs(), mesh), cache_struct, mesh)
    batch_pspecs = _fix_divisibility(
        _filter_axes(batch_pspecs, mesh), structs, mesh)
    param_sh = _named(mesh, pspecs)
    cache_sh = _named(mesh, cache_pspecs)
    batch_sh = _named(mesh, batch_pspecs)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]
    logits_spec = (P(batch_axes, None)
                   if run.shape.global_batch % n_batch_shards == 0
                   else P())
    logits_sh = NamedSharding(mesh, logits_spec)

    def prefill(params, batch):
        return model.prefill(params, batch["tokens"],
                             **{k: v for k, v in batch.items()
                                if k != "tokens"})

    def decode(params, cache, tokens):
        return model.decode(params, cache, tokens, write=decode_write)

    jit_prefill = jax.jit(
        prefill, in_shardings=(param_sh, batch_sh),
        out_shardings=(logits_sh, cache_sh))
    tok_sh = batch_sh["tokens"]
    jit_decode = jax.jit(
        decode, in_shardings=(param_sh, cache_sh, tok_sh),
        out_shardings=(logits_sh, cache_sh),
        donate_argnums=(1,))

    return ServeStep(prefill=jit_prefill, decode=jit_decode, model=model,
                     param_shardings=param_sh, cache_shardings=cache_sh,
                     input_structs=structs)
