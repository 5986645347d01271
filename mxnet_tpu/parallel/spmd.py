"""SPMD trainer — one compiled, sharded train step over a device mesh.

This is the TPU-native replacement for the reference's whole distributed
training path (gluon Trainer + KVStore push/pull + ps-lite servers,
SURVEY.md 3.5): parameters carry NamedShardings chosen by regex rules
(tensor parallelism), the batch is sharded over ``dp`` (and optionally the
sequence over ``sp``), and ONE jit-compiled step does forward, backward,
and the fused optimizer update with XLA inserting every collective
(gradient psum over dp rides ICI — no servers, no key slicing).

Pipeline ('pp') and expert ('ep') axes are accepted in the mesh. Real
microbatch pipeline scheduling lives in ``parallel.pipeline`` —
``GPTPipe`` stacks a model's blocks as stages and runs the GPipe
schedule (``pipeline_apply``: microbatches hop stages via ppermute
inside a scan, remat bounds live activations) under this trainer via
``PIPELINE_RULES``. A 1F1B schedule would only re-order the bubble;
with ``jax.checkpoint`` on each tick the activation footprint is
already O(stages), so GPipe is the deliberate choice here.
"""
from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..base import MXNetError, getenv, register_env
from ..ndarray.ndarray import NDArray, from_jax
from ..ndarray import random as _random
from .. import tracing as _tracing
from .. import optimizer as opt_mod
from ..gluon.block import _bind_params
from ..gluon.parameter import Parameter
from .mesh import kernel_mesh, make_mesh

P = jax.sharding.PartitionSpec

register_env(
    "MXNET_SPMD_REBIND_INPUTS", 0,
    "Multi-process SPMDTrainer jobs: rebind caller NDArrays in place to "
    "their mesh-resident (non-fully-addressable) buffers, saving the "
    "per-step host->device transfer for re-used batches at the cost of "
    "later host reads on the same NDArray raising. Single-process jobs "
    "always rebind. Read per step.")


def _global_put(a, sh):
    """Place a REPLICATED-CONSISTENT host value (params, optimizer
    state, schedule arrays — every process holds the same full value)
    onto a possibly multi-process mesh sharding.

    ``jax.device_put`` cannot target non-addressable devices; each
    process contributes its addressable shards of the common value via
    ``make_array_from_callback`` — the standard multihost placement
    pattern. NOT for per-process batch data (see ``SPMDTrainer._place``:
    local batches are shards of the global batch, not copies of it)."""
    if jax.process_count() == 1:
        return jax.device_put(a, sh)
    if isinstance(a, jax.Array) and not a.is_fully_addressable:
        # already a global array: reshard through the compiled path
        return jax.device_put(a, sh)
    arr = jnp.asarray(a)
    return jax.make_array_from_callback(
        arr.shape, sh, lambda idx: arr[idx])

__all__ = ["PartitionRules", "SPMDTrainer", "DEFAULT_TRANSFORMER_RULES",
           "DATA_PARALLEL_RULES"]


class PartitionRules:
    """Ordered (regex -> PartitionSpec) rules over parameter names.

    First match wins; no match = fully replicated. Specs name mesh axes
    ('tp', 'pp', ...); axes absent from the mesh are dropped.
    """

    def __init__(self, rules: Sequence[Tuple[str, "P"]]) -> None:
        self._rules = [(re.compile(pat), spec) for pat, spec in rules]

    def spec_for(self, name: str, shape: Tuple[int, ...],
                 mesh: "jax.sharding.Mesh") -> "P":
        for pat, spec in self._rules:
            if pat.search(name):
                return _filter_spec(spec, shape, mesh)
        return P()

    def __add__(self, other: "PartitionRules") -> "PartitionRules":
        out = PartitionRules([])
        out._rules = self._rules + other._rules
        return out


def _filter_spec(spec: "P", shape: Tuple[int, ...],
                 mesh: "jax.sharding.Mesh",
                 axis_sizes: Optional[Dict[str, int]] = None) -> "P":
    """Drop axes not in the mesh or not dividing the dim evenly.
    ``axis_sizes`` overrides the divisibility extents (the host-local
    batch path validates a PER-PROCESS shape against the per-process
    mesh extent, not the global axis size)."""
    sizes = axis_sizes if axis_sizes is not None else \
        dict(zip(mesh.axis_names, mesh.devices.shape))
    parts = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            parts.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        keep = tuple(n for n in names
                     if n in sizes and shape[i] % sizes[n] == 0)
        parts.append(keep if len(keep) > 1 else (keep[0] if keep else None))
    parts = parts[:len(shape)]
    return P(*parts)


# Megatron-style rules for the transformer blocks in this repo (BERT and
# friends): column-parallel QKV/FFN-in, row-parallel out/FFN-out,
# vocab-parallel embeddings. Dense weights are (out, in).
DEFAULT_TRANSFORMER_RULES = PartitionRules([
    (r"attn_qkv\.weight$", P("tp", None)),
    (r"attn_out\.weight$", P(None, "tp")),
    (r"ffn1\.weight$", P("tp", None)),
    (r"ffn2\.weight$", P(None, "tp")),
    (r"attn_qkv\.bias$", P("tp")),
    (r"ffn1\.bias$", P("tp")),
    # seq2seq decoder cross-attention (model_zoo.transformer): q and kv
    # projections column-parallel, output row-parallel — same Megatron
    # split as self-attention
    (r"cross_q\.weight$", P("tp", None)),
    (r"cross_q\.bias$", P("tp")),
    (r"cross_kv\.weight$", P("tp", None)),
    (r"cross_kv\.bias$", P("tp")),
    (r"cross_out\.weight$", P(None, "tp")),
    (r"(src|tgt)_embed\.weight$", P("tp", None)),
    (r"word_embed\.weight$", P("tp", None)),
    (r"mlm_bias$", P("tp")),
])

DATA_PARALLEL_RULES = PartitionRules([])  # replicate everything


class SPMDTrainer:
    """Compiled sharded training: forward+backward+update in one program.

    Parameters
    ----------
    block : HybridBlock
        Initialized model; its parameters are re-placed onto the mesh
        according to ``rules`` (in place — the block keeps working for
        eval too).
    loss_fn : callable(outputs, labels) -> per-sample loss NDArray
    optimizer : str or Optimizer
    mesh : jax.sharding.Mesh or dict (passed to make_mesh)
    rules : PartitionRules for tensor/pipeline parallel parameter layout.
    data_spec / label_spec : PartitionSpecs for the batch arguments.
    """

    def __init__(self, block: Any, loss_fn: Callable,
                 optimizer: Any = "sgd",
                 optimizer_params: Optional[Dict[str, Any]] = None,
                 mesh: Any = None,
                 rules: PartitionRules = DATA_PARALLEL_RULES,
                 data_spec: "P" = P("dp"),
                 label_spec: "P" = P("dp"),
                 donate: bool = True,
                 output_transform: Optional[Callable] = None) -> None:
        self.block = block
        self.loss_fn = loss_fn
        # which forward output feeds the loss (default: first of a tuple)
        self._output_transform = output_transform or (
            lambda out: out[0] if isinstance(out, tuple) else out)
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        elif optimizer_params:
            raise MXNetError("optimizer_params requires a string optimizer")
        self.optimizer = optimizer
        if mesh is None or isinstance(mesh, dict):
            mesh = make_mesh(mesh)
        self.mesh = mesh
        self.rules = rules
        self._data_spec = data_spec
        self._label_spec = label_spec

        # SHARED parameters (tied embeddings registered under two names)
        # enter once, under their first name — a duplicate would bind the
        # same buffer twice in the traced step and double-count its grad
        from ..gluon.parameter import dedupe_shared
        self._names, self._params = dedupe_shared(
            (k, p) for k, p in block.collect_params().items()
            if p.is_initialized)
        # place parameters onto the mesh per rules
        self._param_shardings = []
        for name, p in zip(self._names, self._params):
            spec = rules.spec_for(name, tuple(p.shape), mesh)
            sh = jax.sharding.NamedSharding(mesh, spec)
            p._data._data = _global_put(p.data()._data, sh)
            self._param_shardings.append(sh)
        if mesh.size > 1:
            # eager ops may now mix mesh-placed params with fresh
            # single-device arrays; enable the dispatch-path fixup for
            # as long as the placed parameter buffers live
            from ..ndarray import register as _register
            for p in self._params:
                # the NDArray wrapper persists across per-step buffer
                # swaps; its lifetime = the placed parameter's lifetime
                _register.mark_mesh_resident(p._data)

        # optimizer states co-sharded with their parameter
        states = [self.optimizer.create_state_multi_precision(i, p.data())
                  for i, p in enumerate(self._params)]
        self._opt_states = [
            jax.tree_util.tree_map(
                lambda a, s=self._param_shardings[i]: _global_put(a, s),
                st)
            for i, st in enumerate(states)]

        self._step_fn = None
        self._multi_fn = None
        self._step_count = 0
        self._donate = donate
        # prefetched fit() loops may donate batch buffers too (every
        # step gets a fresh batch); toggled by _set_input_donation
        self._donate_inputs = False
        # (spec, shape, leading, host_local) -> NamedSharding: _place
        # runs per input per step — the filtered-spec + sharding build
        # is pure and repeats endlessly for steady-shape training
        self._spec_cache: Dict[Any, Any] = {}
        # (n_inputs, donate_inputs, health_gate) -> jitted step: flag
        # toggles (fit entering/leaving prefetch donation or the health
        # gate) swap back to the SAME jit wrapper instead of re-jitting
        # — a fresh jax.jit wrapper retraces and recompiles even for an
        # identical program
        self._built_steps: Dict[Any, Any] = {}
        # health-sentry gate: when on, the compiled step computes a
        # fused finite-check over loss+grads, gates the whole update on
        # it (a bad step leaves params/state untouched ON DEVICE), and
        # returns a [any_bad, first_bad_index, loss] vector — the
        # guard's single per-step readback (mxnet_tpu.health)
        self._health_gate = False
        self._last_health = None
        # device-resident step counter + value-keyed scalar cache: a
        # fresh host scalar per call (jnp.float32(lr)) is a host->device
        # transfer per step. t lives on device and advances inside the
        # compiled step; lr/wd are placed once per distinct value.
        self._t_dev = None
        # LRU, not clear-at-cap: a cyclic lr schedule (warm restarts)
        # revisits values — a wholesale clear at overflow would re-pay
        # the transfer for EVERY schedule scalar each cycle,
        # while LRU eviction only drops the coldest value
        from collections import OrderedDict as _OD
        self._scalar_cache: "_OD[float, Any]" = _OD()

    _SCALAR_CACHE_CAP = 512

    def _committed_scalar(self, v: float) -> Any:
        key = float(v)
        a = self._scalar_cache.get(key)
        if a is None:
            a = self._scalar_cache[key] = jnp.float32(key)
            if len(self._scalar_cache) > self._SCALAR_CACHE_CAP:
                self._scalar_cache.popitem(last=False)
        else:
            self._scalar_cache.move_to_end(key)
        return a

    def set_health_gate(self, on: bool) -> None:
        """Toggle the in-program health sentry (``fit(health_guard=)``
        flips it).  Changing the flag changes the traced program, so the
        compiled step is invalidated."""
        on = bool(on)
        if self._health_gate == on:
            return
        self._health_gate = on
        self._last_health = None
        self._step_fn = None
        self._multi_fn = None
        if hasattr(self, "_raw_step_fn"):
            del self._raw_step_fn

    def _set_input_donation(self, on: bool) -> None:
        """Donate batch buffers into the compiled step.  Only valid for
        loops that feed every step a FRESH batch (the prefetched fit
        path): donation deletes the input buffer after the call, so a
        re-used batch would read dead memory.  Changing the flag
        changes the jit donation signature, invalidating the step."""
        on = bool(on)
        if self._donate_inputs == on:
            return
        self._donate_inputs = on
        self._step_fn = None

    # ------------------------------------------------------------------
    def _build_step(self, n_inputs: int) -> Callable:
        body = self._build_step_body(n_inputs,
                                     health_gate=self._health_gate)

        def step(param_arrays, opt_states, rng, lr, wd, t, *batch):
            # the device-side step counter advances INSIDE the program
            # (trailing t+1 output fed back as next step's t): the loop
            # used to dispatch a separate tiny increment program per
            # step
            return body(param_arrays, opt_states, rng, lr, wd, t,
                        *batch) + (t + 1.0,)

        donate = (0, 1) if self._donate else ()
        if not self._donate_inputs:
            return self._program(step, donate)
        # batch args start at position 6; n_inputs data arrays plus
        # the label array.  Batch buffers rarely alias an output shape
        # (params/states/loss) — the donation win is the EARLY release
        # of the consumed batch's device memory, so XLA's "donated
        # buffers were not usable" aliasing warning is expected noise:
        # filter it ONCE, message-scoped, at build time (a per-call
        # warnings.catch_warnings() mutates process-global state and is
        # documented thread-unsafe against the prefetch thread)
        import warnings as _warnings
        _warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        donate = donate + tuple(range(6, 6 + n_inputs + 1))
        return self._program(step, donate)

    def _program(self, fn: Callable, donate: Tuple[int, ...]) -> Callable:
        """The compiled step, with its line in ``tracing.programs()``."""
        return _tracing.program(
            fn, "train_step", type(self.block).__name__,
            attrs={"optimizer": type(self.optimizer).__name__,
                   "devices": int(self.mesh.size)},
            donate_argnums=donate)

    def _build_step_body(self, n_inputs: int,
                         health_gate: bool = False) -> Callable:
        block, loss_fn = self.block, self.loss_fn
        mesh = self.mesh
        params = self._params
        optimizer = self.optimizer
        hp = [optimizer._hyper(i) for i in range(len(params))]
        opt_cls = type(optimizer)
        # mesh axes the batch dim is sharded over (data_spec's first entry)
        lead = self._data_spec[0] if len(self._data_spec) else None
        batch_axes = lead if isinstance(lead, tuple) else \
            ((lead,) if lead else ())

        def step(param_arrays, opt_states, rng, lr, wd, t, *batch):
            inputs, labels = list(batch[:-1]), batch[-1]

            def forward(pa):
                from .ring import sequence_parallel
                from .moe import collect_aux_losses
                import contextlib
                sp_ctx = (sequence_parallel(mesh, "sp")
                          if "sp" in mesh.axis_names
                          else contextlib.nullcontext())
                # Pallas kernels must be shard_mapped over the mesh the
                # step compiles for (GSPMD cannot partition them)
                km_ctx = (kernel_mesh(mesh, batch_axes)
                          if mesh.size > 1 else contextlib.nullcontext())
                with _bind_params(params, pa), _random.trace_key_scope(rng), \
                        sp_ctx, km_ctx, collect_aux_losses() as aux_losses:
                    from .._tape import set_training
                    prev = set_training(True)
                    try:
                        out = block.forward(
                            *[from_jax(b) for b in inputs])
                    finally:
                        set_training(prev)
                    out = self._output_transform(out)
                    with jax.named_scope("loss"):
                        loss = loss_fn(out, from_jax(labels))
                        # loss is already MEAN-reduced here, so grads
                        # need no 1/batch rescale (unlike the Trainer
                        # path, which rescales summed per-sample grads)
                        total = loss.mean()._data
                        # MoE load-balancing terms raised during forward
                        for a in aux_losses:
                            total = total + a._data
                    # in-trace writes to non-differentiable state (BN
                    # running stats), read BEFORE _bind_params restores
                    from ..gluon.block import _collect_mutated
                    mut = dict(_collect_mutated(params, pa))
                    return total, mut

            if getattr(block, "schedule", None) == "1f1b" and \
                    callable(getattr(block, "pipeline_loss_and_grads",
                                     None)):
                # pipeline blocks with a hand-scheduled 1F1B sweep own
                # their gradient computation — interleaved fwd/bwd with
                # an S-slot residual ring instead of jax.grad over the
                # whole GPipe schedule. Training mode and the trainer's
                # output transform apply exactly as on the autodiff path.
                from .._tape import set_training
                prev = set_training(True)
                try:
                    loss, grads, mut = block.pipeline_loss_and_grads(
                        params, list(param_arrays), inputs, labels,
                        loss_fn, rng,
                        output_transform=self._output_transform)
                finally:
                    set_training(prev)
            else:
                (loss, mut), grads = jax.value_and_grad(
                    forward, has_aux=True)(list(param_arrays))
            for i in mut:
                if params[i].grad_req != "null":
                    raise MXNetError(
                        f"parameter {self._names[i]!r} (grad_req="
                        f"{params[i].grad_req!r}) was reassigned during "
                        "forward; only non-differentiable state may be "
                        "mutated in-trace — its optimizer update would "
                        "be silently discarded")
            ok = None
            health = None
            if health_gate:
                # fused finite/overflow reduction over the loss and
                # every live gradient — ONE traced reduction, no
                # per-tensor host syncs; index 0 is the loss, i+1 is
                # parameter i (for the guard's culprit naming)
                flags = [jnp.logical_not(jnp.all(jnp.isfinite(loss)))]
                for i, g in enumerate(grads):
                    if params[i].grad_req != "null" and i not in mut:
                        flags.append(jnp.logical_not(
                            jnp.all(jnp.isfinite(g))))
                    else:
                        flags.append(jnp.zeros((), jnp.bool_))
                badv = jnp.stack(flags)
                any_bad = badv.any()
                ok = jnp.logical_not(any_bad)
                health = jnp.stack([any_bad.astype(jnp.float32),
                                    jnp.argmax(badv).astype(jnp.float32),
                                    loss.astype(jnp.float32)])
            def apply_updates(args):
                pa, sts, gs, mt = args
                new_params, new_states = [], []
                for i, (w, g, st) in enumerate(zip(pa, gs, sts)):
                    if i in mt:
                        # forward-mutated state advances by its traced
                        # update; it must NOT get an optimizer step (wd
                        # would decay BN running stats — zero grad does
                        # not mean no-op)
                        new_params.append(mt[i])
                        new_states.append(st)
                    elif params[i].grad_req == "null":
                        new_params.append(w)
                        new_states.append(st)
                    else:
                        with jax.named_scope("optim"):
                            nw, ns = opt_cls._step(w, g, st, lr, wd, t,
                                                   hp[i])
                        new_params.append(nw)
                        new_states.append(ns)
                return new_params, new_states

            operands = (list(param_arrays), list(opt_states),
                        list(grads), mut)
            if ok is None:
                new_params, new_states = apply_updates(operands)
                return new_params, new_states, loss
            # gate the whole update on the sentry verdict with ONE
            # lax.cond: a bad step takes the identity branch (params,
            # optimizer state, and BN running stats all untouched —
            # buffer-forwarded, no per-tensor where doubling the
            # update's memory traffic on the common clean path)
            new_params, new_states = jax.lax.cond(
                ok, apply_updates,
                lambda args: (list(args[0]), list(args[1])), operands)
            return new_params, new_states, loss, health

        return step

    def _build_multi_step(self, n_inputs: int) -> Callable:
        """K steps fused into one program via lax.scan — the TPU analog
        of the reference's engine op bulking (MXNET_EXEC_BULK_EXEC_TRAIN):
        one dispatch, one set of output buffers, no per-step host
        round-trips."""
        raw_step = self._raw_step(n_inputs)

        def multi(param_arrays, opt_states, keys, lrs, wds, t0, *batches):
            xs, ys = list(batches[:-1]), batches[-1]

            def body(carry, inp):
                params, states, t = carry
                key, lr, wd = inp[0], inp[1], inp[2]
                step_inputs = inp[3:]
                new_p, new_s, loss = raw_step(
                    params, states, key, lr, wd, t, *step_inputs)
                return (new_p, new_s, t + 1.0), loss

            (params, states, _), losses = jax.lax.scan(
                body, (list(param_arrays), list(opt_states), t0),
                (keys, lrs, wds) + tuple(xs) + (ys,))
            return params, states, losses

        return self._program(multi, (0, 1) if self._donate else ())

    def _raw_step(self, n_inputs: int) -> Callable:
        """The unjitted single-step body (shared by step and multi-step)."""
        if not hasattr(self, "_raw_step_fn") or \
                self._raw_step_n != n_inputs:
            self._raw_step_fn = self._build_step_body(n_inputs)
            self._raw_step_n = n_inputs
        return self._raw_step_fn

    def _check_graph_epoch(self) -> None:
        """Invalidate the compiled step when host-side layer state changed
        the traced program (BatchNorm cold-start bootstrap runs exactly
        once: the step after it must re-trace to the blend graph)."""
        from ..gluon.block import graph_epoch, _remat_enabled
        # env knobs that change the traced program invalidate
        # UNCONDITIONALLY — the _epoch_sensitive filter below only
        # covers layer-state epochs (BatchNorm), not trace-time flags
        remat = _remat_enabled()
        if getattr(self, "_remat_flag", None) != remat:
            self._remat_flag = remat
            self._step_fn = None
            self._multi_fn = None
            self._built_steps.clear()
            if hasattr(self, "_raw_step_fn"):
                del self._raw_step_fn
        epoch = graph_epoch()
        if getattr(self, "_graph_epoch", None) != epoch:
            self._graph_epoch = epoch
            if not getattr(self.block, "_epoch_sensitive", lambda: True)():
                return      # traced program cannot have changed
            self._step_fn = None
            self._multi_fn = None
            self._built_steps.clear()
            if hasattr(self, "_raw_step_fn"):
                del self._raw_step_fn

    def _place(self, x: Any, spec: "P",
               leading_step_dim: bool = False) -> Any:
        """Put a batch input onto the mesh per ``spec`` (with an unsharded
        leading K dimension for the fused multi-step path) and write the
        mesh-resident buffer back into the NDArray, so a re-used batch
        is resharded onto the mesh once, not on every step."""
        a = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        multi = jax.process_count() > 1
        host_local = multi and not (
            isinstance(a, jax.Array) and not a.is_fully_addressable)
        orig = spec
        cache_key = (orig, tuple(a.shape), leading_step_dim, host_local)
        cached = self._spec_cache.get(cache_key)
        if cached is not None:
            spec, sh = cached
        else:
            # a host-local batch is a PER-PROCESS shard: its dims must
            # divide the per-process mesh extent, not the global axis
            # size (a local batch of 2 on a dp=4 mesh over 2 processes
            # is valid — 2 local devices each)
            sizes = (dict(zip(self.mesh.axis_names,
                              self.mesh.local_mesh.devices.shape))
                     if host_local else None)
            shape = tuple(a.shape[1:] if leading_step_dim else a.shape)
            spec = _filter_spec(orig, shape, self.mesh, axis_sizes=sizes)
            if host_local:
                # for host-local data a dropped-for-divisibility axis
                # CHANGES MEANING (shard of the global batch -> claimed
                # copy of it), so it must error, not silently replicate
                # inconsistent data
                membership = _filter_spec(
                    orig, shape, self.mesh,
                    axis_sizes={n: 1 for n in self.mesh.axis_names})
                if tuple(spec) != tuple(membership):
                    raise MXNetError(
                        f"per-process batch shape {shape} does not "
                        f"divide the local mesh extent "
                        f"{dict((k, v) for k, v in sizes.items())} for "
                        f"spec {orig}; each process's local batch must "
                        "split evenly over its own devices")
            if leading_step_dim:
                spec = P(*((None,) + tuple(spec)))
            sh = jax.sharding.NamedSharding(self.mesh, spec)
            if len(self._spec_cache) > 64:     # few live shapes; bound it
                self._spec_cache.clear()
            self._spec_cache[cache_key] = (spec, sh)
        cur = getattr(a, "sharding", None)
        if cur is not None and (cur == sh or (
                hasattr(cur, "is_equivalent_to") and
                cur.is_equivalent_to(sh, a.ndim))):
            return a
        if host_local:
            # this process's shard of the global batch (reference
            # dist_sync semantics: every worker feeds its own local data)
            from jax.experimental import multihost_utils
            a = multihost_utils.host_local_array_to_global_array(
                jnp.asarray(a), self.mesh, spec)
        elif multi:
            a = jax.device_put(a, sh)           # global array: reshard
        else:
            a = _global_put(a, sh)
        if isinstance(x, NDArray) and (
                not multi or bool(getenv("MXNET_SPMD_REBIND_INPUTS", 0))):
            # write the mesh-resident buffer back into the caller's NDArray
            # so re-used batches skip the host->device transfer on every
            # step. Multi-process jobs skip the rebind by default — there
            # the buffer is non-fully-addressable and a later asnumpy()/
            # metric read on the caller's array would raise (opt back in
            # with MXNET_SPMD_REBIND_INPUTS=1 when inputs are step-only).
            x._data = a
            if getattr(a, "sharding", None) is not None \
                    and a.sharding.num_devices > 1:
                # the caller's wrapper may outlive the trainer: keep the
                # harmonization scan alive while it does
                from ..ndarray.register import mark_mesh_resident
                mark_mesh_resident(x)
        return a

    def run_steps(self, data: Any, labels: Any) -> NDArray:
        """Run K fused steps: ``data``/``labels`` carry a leading step
        dimension (K, batch, ...). Returns the (K,) per-step losses.
        Parameters/optimizer state advance K times on device.

        Like :meth:`step`, input NDArrays are rebound in place to their
        mesh-resident shardings (see the step() docstring for the
        multi-process caveat).
        """
        import time
        from .. import metrics as _metrics
        inputs = data if isinstance(data, (list, tuple)) else [data]

        t0 = time.perf_counter()
        arrays = [self._place(x, self._data_spec, leading_step_dim=True)
                  for x in inputs]
        label_arr = self._place(labels, self._label_spec,
                                leading_step_dim=True)
        t_data = time.perf_counter() - t0
        K = arrays[0].shape[0]
        self._check_graph_epoch()
        if self._multi_fn is None:
            self._multi_fn = self._build_multi_step(len(arrays))
        rng = _random.split_key()
        keys = jax.random.split(rng, K)
        # per-step lr/wd so schedules advance exactly as K single steps
        base = self._step_count
        lrs, wds = [], []
        for i in range(1, K + 1):
            self.optimizer.num_update = base + i
            lrs.append(self.optimizer.learning_rate)
            wds.append(self.optimizer.wd)
        param_arrays = [p.data()._data for p in self._params]
        lrs_a = jnp.asarray(lrs, jnp.float32)
        wds_a = jnp.asarray(wds, jnp.float32)
        t0_a = jnp.float32(base + 1)
        # donated param/state buffers: pending bulked segments holding
        # them BY VALUE must materialize first (targeted — the prefetch
        # thread's in-build segment never captured them and keeps going)
        from .. import bulk as _bulk
        _bulk.flush_holding(
            param_arrays + jax.tree_util.tree_leaves(self._opt_states),
            "mutation")
        new_params, new_states, losses = self._multi_fn(
            param_arrays, self._opt_states, keys,
            lrs_a, wds_a, t0_a, *arrays, label_arr)
        self._step_count += K
        self.optimizer.num_update = self._step_count
        self._t_dev = None  # re-sync the device counter on next step()
        for p, a in zip(self._params, new_params):
            p.data()._data = a
        self._opt_states = new_states
        total = time.perf_counter() - t0
        _metrics.record_step(total, data=t_data,
                             dispatch=total - t_data, count=K)
        _metrics.record_device_highwater()
        return from_jax(losses)

    def step(self, data: Any, labels: Any, batch_size: Optional[int] = None
             ) -> NDArray:
        """One training step; returns the (replicated) scalar loss.

        Input NDArrays are rebound in place to their mesh-resident
        shardings so a re-used batch pays its host->device transfer only
        once. In multi-process jobs the rebound buffer is a global
        (non-host-addressable) array: per-process host-side reads of the
        same NDArray (``asnumpy``, eager ops, metrics) must use a separate
        copy of the data.
        """
        # a real span: a caller's own loop over step() has no trace, so
        # this roots one (inside fit() it nests under train.step) and
        # step.place / step.dispatch below are recorded for everyone
        with _tracing.span("spmd.step", step=self._step_count):
            return self._step(data, labels)

    def _step(self, data: Any, labels: Any) -> NDArray:
        import time
        from .. import metrics as _metrics
        inputs = data if isinstance(data, (list, tuple)) else [data]

        t0 = time.perf_counter()
        with _tracing.child_span("step.place"):
            arrays = [self._place(x, self._data_spec) for x in inputs]
            label_arr = self._place(labels, self._label_spec)
        t_data = time.perf_counter() - t0
        from .. import faults as _faults
        if _faults._ARMED:
            # tensor-corrupting chaos site: kind=nan poisons the first
            # float tensor among data + labels, making the compiled
            # step's gradients non-finite — the deterministic trigger
            # the health sentry trains against
            corr = _faults.maybe_corrupt(
                "trainer.step", list(arrays) + [label_arr],
                step=self._step_count)
            arrays, label_arr = corr[:-1], corr[-1]
        self._check_graph_epoch()
        if self._step_fn is None:
            key = (len(arrays), self._donate_inputs, self._health_gate)
            fn = self._built_steps.get(key)
            if fn is None:
                fn = self._built_steps[key] = \
                    self._build_step(len(arrays))
            self._step_fn = fn
        self._step_count += 1
        self.optimizer.num_update = self._step_count
        lr = self.optimizer.learning_rate
        wd = self.optimizer.wd
        rng = _random.split_key()
        param_arrays = [p.data()._data for p in self._params]
        if self._t_dev is None:
            # (re-)sync the device-resident step counter; afterwards it
            # advances inside the compiled step (trailing t+1 output)
            self._t_dev = self._committed_scalar(float(self._step_count))
        # the compiled step donates param/state buffers (and, on the
        # prefetched fit path, the batch buffers): any pending bulked
        # segment still holding one BY VALUE must materialize first.
        # Targeted — NOT flush_all: a global flush here cut the prefetch
        # thread's in-build preprocessing segment once per step,
        # re-serializing exactly the work the input pipeline overlaps
        from .. import bulk as _bulk
        donated = param_arrays + jax.tree_util.tree_leaves(
            self._opt_states)
        if self._donate_inputs:
            donated = donated + list(arrays) + [label_arr]
        _bulk.flush_holding(donated, "mutation")
        with _tracing.child_span("step.dispatch"):
            out = self._step_fn(
                param_arrays, self._opt_states, rng,
                self._committed_scalar(lr), self._committed_scalar(wd),
                self._t_dev,
                *arrays, label_arr)
        if self._health_gate:
            new_params, new_states, loss, self._last_health, \
                self._t_dev = out
        else:
            new_params, new_states, loss, self._t_dev = out
        for p, a in zip(self._params, new_params):
            p.data()._data = a
        self._opt_states = new_states
        total = time.perf_counter() - t0
        # dispatch-side accounting: the program is still running on
        # device when step() returns — the caller's loss sync is the
        # mxnet_step_sync_seconds component (estimator/bench observe it)
        _metrics.record_step(total, data=t_data,
                             dispatch=total - t_data)
        _metrics.record_device_highwater()
        return from_jax(loss)

    @property
    def learning_rate(self) -> float:
        return self.optimizer.learning_rate

    def input_placement(self) -> Callable[[Any], Any]:
        """A ``(data, labels) -> (data, labels)`` callable committing a
        batch onto this trainer's mesh shardings.

        ``DevicePrefetcher.attach(trainer)`` installs it as the
        prefetcher's placement: the background thread then pays the
        host->device transfer of batch N+1 while step N executes, and
        ``step()``'s own ``_place`` short-circuits on the already-
        matching sharding (no second copy).

        Multi-process jobs keep placement at step time (identity here):
        ``_place`` there runs ``host_local_array_to_global_array`` — a
        cross-process collective that must interleave identically on
        every process, which a background thread cannot guarantee
        against the step's own collectives — and skips the in-place
        rebind, so prefetch-thread placement work would be discarded
        anyway.  The prefetcher still overlaps the host fetch +
        preprocessing."""
        if jax.process_count() > 1:
            return lambda batch: batch

        def one(x: Any, spec: "P") -> Any:
            if not isinstance(x, NDArray):
                x = from_jax(jnp.asarray(x))
            self._place(x, spec)       # rebinds x._data mesh-resident
            return x

        def place(batch: Any) -> Any:
            data, labels = batch
            if isinstance(data, (list, tuple)):
                data = type(data)(one(x, self._data_spec) for x in data)
            else:
                data = one(data, self._data_spec)
            labels = one(labels, self._label_spec)
            return data, labels

        return place

    # -- preemption-safe training loop ---------------------------------
    def fit(self, batch_fn: Any, num_steps: int,
            checkpoint_manager: Any = None,
            checkpoint_every: int = 10,
            health_guard: Any = None) -> Optional[NDArray]:
        """Run up to ``num_steps`` steps with auto-resume and graceful
        preemption — the kill-and-restart-safe loop.

        ``batch_fn``: a callable ``step -> (data, labels)`` (preferred —
        resume re-derives the exact batch for any step), an iterable
        of ``(data, labels)`` (on resume, the first ``restored_step``
        batches are consumed and discarded to stay on-schedule), or a
        :class:`~mxnet_tpu.io.DevicePrefetcher` wrapping either.  A
        callable-mode prefetcher is driven directly: host fetch +
        sharded device placement of batch N+1 overlap step N on the
        prefetch thread, batch buffers are donated to the compiled step
        (``MXNET_PREFETCH_DONATE``), and checkpoint resume / HealthGuard
        rewind invalidate queued batches transparently.

        With ``checkpoint_manager``: restores the newest verified
        checkpoint before the first step (making the call idempotent
        under kill-and-restart — a rerun continues where the kill
        landed, and a completed run is a no-op), saves every
        ``checkpoint_every`` steps, and saves a final checkpoint at
        ``num_steps``.  A
        :class:`~mxnet_tpu.checkpoint.CoordinatedCheckpointManager`
        slots in unchanged: every rank then agrees on the checkpoint
        step through the two-phase cluster rendezvous before any rank
        commits, and the restore resumes the whole cluster from one
        consistent step; the rendezvous is hang-watchdog-armed
        (``checkpoint.save`` site) and a dead rank is named in a
        structured error instead of stalling the save.  A SIGTERM/SIGINT during the loop finishes the
        in-flight step, writes a checkpoint, and returns cleanly
        (:class:`~mxnet_tpu.preemption.PreemptionGuard`); the next
        incarnation resumes from it.

        With ``health_guard`` (:class:`mxnet_tpu.health.HealthGuard`):
        the compiled step gains an in-program numerics sentry that
        gates the whole update on-device (a NaN/Inf step never touches
        parameters or optimizer state), the guard reads one small
        health vector per step and applies its skip/rewind/abort
        policy, and the hang watchdog arms around every step.  Rewind
        needs BOTH a ``checkpoint_manager`` and a callable ``batch_fn``
        (an iterable cannot replay); ``batch_fn(step, salt=...)`` is
        used when the callable accepts a ``salt`` keyword, so replays
        after a rewind perturb the data order.

        Returns the loss of the last executed step (``None`` if there
        was nothing left to run).  Only that one loss is fetched — the
        loop itself never syncs on the device (a ``health_guard`` adds
        its single per-step readback).
        """
        from ..preemption import PreemptionGuard
        from ..io.prefetch import DevicePrefetcher
        if checkpoint_manager is not None:
            checkpoint_manager.restore(self)
        start = self._step_count
        prefetcher: Optional[DevicePrefetcher] = None
        if isinstance(batch_fn, DevicePrefetcher) and batch_fn.is_callable:
            # the prefetched loop: batch N+1 is fetched, preprocessed,
            # and committed to this trainer's mesh shardings on the
            # prefetcher's background thread WHILE step N executes —
            # get() below is a queue pop of a device-resident batch.
            # A resume (non-consecutive step) or a HealthGuard rewind
            # (changed salt) invalidates queued batches automatically.
            prefetcher = batch_fn.attach(self)
            if prefetcher.takes_salt and health_guard is not None:
                def get_batch(step):
                    return prefetcher.get(
                        step, salt=health_guard.replay_salt)
            else:
                def get_batch(step):
                    return prefetcher.get(step)
            if prefetcher.donate:
                # every step gets a FRESH device-resident batch, so its
                # buffers can be donated into the compiled step (XLA
                # reuses the input memory for outputs)
                self._set_input_donation(True)
        elif callable(batch_fn):
            from ..io.prefetch import takes_salt as _takes_salt
            if _takes_salt(batch_fn) and health_guard is not None:
                def get_batch(step):
                    return batch_fn(step, salt=health_guard.replay_salt)
            else:
                get_batch = batch_fn
        else:
            it = iter(batch_fn)

            def get_batch(step, _it=it):
                try:
                    return next(_it)
                except StopIteration:
                    raise MXNetError(
                        f"batch iterable exhausted at step {step} "
                        f"(num_steps={num_steps}); pass a callable "
                        "batch_fn (step -> batch) or a long-enough "
                        "iterable") from None

            for s in range(start):      # skip batches already trained on
                get_batch(s)
        import contextlib
        if health_guard is not None:
            self.set_health_gate(True)
            if checkpoint_manager is not None and (
                    prefetcher is not None or callable(batch_fn)):
                # a callable-mode prefetcher replays like a bare
                # batch_fn: the rewind's non-consecutive step (and
                # perturbed salt) invalidates its queue and reseeks
                health_guard.set_rewind(
                    lambda: checkpoint_manager.restore(self))
        loss: Optional[NDArray] = None
        try:
            with PreemptionGuard() as guard:
                # the sentry verdict for step N is read while step N+1
                # is already in flight (`prev` holds the un-verified
                # step's health vector + loss): the readback then
                # overlaps device compute instead of stalling the
                # pipeline every step.  Verifying one step late is
                # sound BECAUSE the update is gated on-device — a bad
                # step never touched parameters, so any checkpoint
                # written in the detection gap is still clean.
                prev = None
                while True:
                    cur = None
                    ran = self._step_count < num_steps
                    if ran:
                        step = self._step_count
                        # per-step root span: batch get (prefetch pop
                        # or host fetch) and the step dispatch are its
                        # children — a slow step tail-upgrades the
                        # whole tree into the trace ring
                        with _tracing.span("train.step", step=step):
                            data, labels = get_batch(step)
                            with (health_guard.watch("trainer.step",
                                                     step=step)
                                  if health_guard is not None
                                  else contextlib.nullcontext()):
                                step_loss = self.step(data, labels)
                        if health_guard is None:
                            loss = step_loss
                        else:
                            cur = (self._last_health, step_loss)
                            try:     # start the readback without blocking
                                cur[0].copy_to_host_async()
                            except Exception:   # noqa: BLE001 - backend-
                                pass            # dependent surface
                    if health_guard is not None and prev is not None:
                        verdict = health_guard.check_device(
                            prev[0], names=self._names)
                        if verdict.action == "rewind":
                            if health_guard.do_rewind() is not None:
                                # restored the newest verified
                                # checkpoint (replay gets a perturbed
                                # salt); the in-flight step built on
                                # abandoned state — discard it, restore
                                # overwrites everything
                                prev = None
                                continue
                            # nothing to restore to (no checkpoint yet;
                            # accounted as a skip): the gated bad step
                            # never landed, the in-flight step is still
                            # valid — keep pipelining
                        elif verdict.ok:
                            # a skipped step's loss is the garbage that
                            # triggered the skip — the returned "last
                            # loss" tracks accepted steps only
                            loss = prev[1]
                    prev = cur
                    done = self._step_count
                    preempted = guard.requested
                    need_ckpt = ran and checkpoint_manager is not None \
                        and (preempted or done == num_steps
                             or (checkpoint_every > 0
                                 and done % checkpoint_every == 0))
                    if need_ckpt and health_guard is not None \
                            and prev is not None:
                        # a checkpoint must never capture an UNVERIFIED
                        # step: it would become the newest "verified"
                        # rewind target, and a rewind to it would
                        # silently never replay the bad step.  Drain
                        # this step's verdict synchronously (only
                        # checkpoint-boundary steps pay the stall).
                        hv, pl = prev
                        prev = None
                        verdict = health_guard.check_device(
                            hv, names=self._names)
                        if verdict.action == "rewind":
                            if health_guard.do_rewind() is not None:
                                continue      # restored: skip the save
                            # no-op rewind (no checkpoint yet, counted
                            # as a skip): state is clean — save anyway
                        elif verdict.ok:
                            loss = pl
                    if need_ckpt:
                        # watchdog-armed: a coordinated save blocks in
                        # the cluster rendezvous — a hang here (wedged
                        # peer) dumps stacks instead of stalling silent
                        from .. import health as _health
                        with _tracing.span("checkpoint.save",
                                           step=done), \
                                _health.watch_section("checkpoint.save",
                                                      step=done):
                            checkpoint_manager.save(self, step=done)
                    if preempted:
                        # drain the pending verdict so accounting and
                        # the returned loss cover the final step.  Only
                        # the manager-less path can still hold one here,
                        # and without a rewind action the policy already
                        # degrades to skip — no rewind can be decided
                        # during shutdown.
                        if health_guard is not None and prev is not None:
                            verdict = health_guard.check_device(
                                prev[0], names=self._names)
                            if verdict.ok:
                                loss = prev[1]
                        break
                    if self._step_count >= num_steps and prev is None:
                        break
        finally:
            if health_guard is not None:
                self.set_health_gate(False)
            if prefetcher is not None and prefetcher.donate:
                # manual step() calls after fit must not have their
                # batch buffers deleted under them
                self._set_input_donation(False)
        return loss

    # -- checkpoint / resume (reference SURVEY.md 5.4: .params format +
    # sharded device-resident trainer state keyed by param names) --------
    def save_checkpoint(self, prefix: str) -> None:
        """Write ``prefix.params`` (reference-format, interop-safe) and
        ``prefix.states`` (optimizer state + step count).  Sharded arrays
        are gathered to host; shardings are re-applied on load."""
        import pickle
        import numpy as onp
        from .. import ndarray_io
        ndarray_io.save_params(
            prefix + ".params",
            {n: from_jax(p.data()._data)
             for n, p in zip(self._names, self._params)})
        payload = {
            "step_count": self._step_count,
            "opt_states": [jax.tree_util.tree_map(onp.asarray, s)
                           for s in self._opt_states],
            "names": self._names,
        }
        with open(prefix + ".states", "wb") as f:
            pickle.dump(payload, f)

    def load_checkpoint(self, prefix: str) -> None:
        """Restore a :meth:`save_checkpoint`; parameters and optimizer
        state land back on the mesh with their recorded shardings."""
        import pickle
        from .. import ndarray_io
        # validate EVERYTHING before touching live state: a mismatched
        # checkpoint must not leave the trainer half-loaded
        loaded = ndarray_io.load_params(prefix + ".params")
        missing = [n for n in self._names if n not in loaded]
        if missing:
            raise MXNetError(f"checkpoint {prefix}.params missing "
                             f"parameters {missing}")
        with open(prefix + ".states", "rb") as f:
            payload = pickle.load(f)
        if payload["names"] != self._names:
            raise MXNetError("checkpoint parameter names do not match "
                             "this trainer's model")
        for name, p, sh in zip(self._names, self._params,
                               self._param_shardings):
            p._data._data = _global_put(loaded[name]._data, sh)
        self._step_count = payload["step_count"]
        self.optimizer.num_update = self._step_count
        self._t_dev = None  # re-sync the device counter on next step()
        self._opt_states = [
            jax.tree_util.tree_map(
                lambda a, s=sh: _global_put(jnp.asarray(a), s), st)
            for st, sh in zip(payload["opt_states"],
                              self._param_shardings)]
