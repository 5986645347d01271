"""Trainer — applies an optimizer over a block's parameters.

Reference parity (leezu/mxnet): ``python/mxnet/gluon/trainer.py`` — kvstore
wiring (``update_on_kvstore`` decision, ``allreduce_grads``), per-param
fused optimizer updates, ``save_states/load_states`` exact-resume.

Design (tpu-first): data-parallel gradient reduction happens either through
a KVStore ('device'/'ici' → psum over the mesh, see ``kvstore.py``) or is a
no-op on one chip. Parameters keep a single (possibly sharded) buffer, so
there is no per-device copy fan-out to manage.
"""
from __future__ import annotations

import io
import pickle
import weakref
from typing import Any, Dict, List, Optional, Sequence, Union

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .. import optimizer as opt
from .. import tracing as _tracing
from .parameter import Parameter

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params: Any, optimizer: Union[str, "opt.Optimizer"],
                 optimizer_params: Optional[Dict[str, Any]] = None,
                 kvstore: Union[str, Any, None] = "device",
                 compression_params: Optional[Dict[str, Any]] = None,
                 update_on_kvstore: Optional[bool] = None) -> None:
        if isinstance(params, dict):
            named = list(params.items())
        elif isinstance(params, (list, tuple)):
            named = [(getattr(p, "name", str(i)), p)
                     for i, p in enumerate(params)]
        else:
            raise MXNetError(
                "Trainer expects a ParameterDict (from collect_params()) or "
                f"a list of Parameters, got {type(params)}")
        for _, p in named:
            if not isinstance(p, Parameter):
                raise MXNetError(f"non-Parameter {p!r} passed to Trainer")
        from .parameter import dedupe_shared
        self._param_names, self._params = dedupe_shared(named)
        self._params_to_init: List[Parameter] = []

        optimizer_params = optimizer_params or {}
        if isinstance(optimizer, opt.Optimizer):
            if optimizer_params:
                raise MXNetError("optimizer_params must be None when "
                                 "optimizer is an Optimizer instance")
            self._optimizer = optimizer
        else:
            self._optimizer = opt.create(optimizer, **optimizer_params)
        # param_dict drives lr_mult/wd_mult lookups by index
        self._optimizer.param_dict = dict(enumerate(self._params))

        self._states: Dict[int, Any] = {}
        self._kvstore_arg = kvstore
        self._compression_params = compression_params
        self._update_on_kvstore_arg = update_on_kvstore
        self._update_on_kvstore = False
        self._kvstore = None
        self._kv_initialized = False
        self._scale = 1.0
        # event-driven gradient streaming (per-layer backward overlap):
        # the round armed for the NEXT step, its planned grad wrappers,
        # the per-key staging buffers reduced values land in, and the
        # dirty latch a second backward-before-step trips
        self._stream_round = None
        self._stream_vals: Dict[int, Any] = {}
        self._stream_bufs: Dict[int, Any] = {}
        self._stream_staging: Dict[int, NDArray] = {}
        self._stream_dirty = False
        self._stream_cbs_installed = False

    # -- kvstore ------------------------------------------------------------
    def _init_kvstore(self) -> None:
        from .. import kvstore as kvs
        if self._kvstore_arg is None:
            self._kvstore = None
        elif isinstance(self._kvstore_arg, str):
            self._kvstore = kvs.create(self._kvstore_arg)
        else:
            self._kvstore = self._kvstore_arg
        if self._kvstore is not None and self._compression_params:
            self._kvstore.set_gradient_compression(self._compression_params)
        # update_on_kvstore (reference trainer.py decision): explicit
        # argument wins; default True only for the async parameter
        # service, whose whole point is server-side updates. The store
        # then owns weights AND optimizer — ship both.
        if self._kvstore is not None:
            auto = getattr(self._kvstore, "type", "") == "dist_async"
            if auto and self._update_on_kvstore_arg is not None \
                    and not self._update_on_kvstore_arg:
                # the async service has no worker-count-aware per-round
                # aggregation: without a server-side optimizer, pulls
                # return running gradient SUMS since init, not per-step
                # reductions — reject rather than silently mistrain
                raise MXNetError(
                    "kvstore='dist_async' requires updates on the "
                    "kvstore (the server applies the optimizer per "
                    "push); update_on_kvstore=False is not supported — "
                    "use kvstore='ici' for worker-side updates")
            self._update_on_kvstore = (auto
                                       if self._update_on_kvstore_arg is None
                                       else bool(self._update_on_kvstore_arg))
        if self._update_on_kvstore:
            # For a SHARED remote store (dist_async: one server-side copy)
            # rank 0 alone seeds weights and ships the optimizer, THEN
            # everyone crosses the barrier — a later init would race and a
            # later set_optimizer would reset server momentum. Per-process
            # stores (local/device/ici) hold per-rank state: every rank
            # must seed its own copy and updater.
            shared = getattr(self._kvstore, "type", "") == "dist_async"
            if not shared or getattr(self._kvstore, "rank", 0) == 0:
                for i, p in enumerate(self._params):
                    if p.grad_req != "null" and p.is_initialized:
                        self._kvstore.init(i, p.data())
                self._kvstore.set_optimizer(self._optimizer)
            if shared and hasattr(self._kvstore, "barrier"):
                self._kvstore.barrier()
                # EVERY rank starts from the server's seeded weights
                # (the reference broadcasts initial params via kvstore
                # init + pull) — without this, ranks > 0 would compute
                # their first gradient at their own local random init,
                # pushing updates unrelated to the served model
                keys = [i for i, p in enumerate(self._params)
                        if p.grad_req != "null" and p.is_initialized]
                if keys:
                    self._kvstore.pull(
                        keys, out=[self._params[i].data() for i in keys])
        self._kv_initialized = True

    @property
    def learning_rate(self) -> float:
        return self._optimizer.learning_rate

    @property
    def optimizer(self) -> "opt.Optimizer":
        return self._optimizer

    def set_learning_rate(self, lr: float) -> None:
        self._optimizer.set_learning_rate(lr)

    # -- core step ----------------------------------------------------------
    def _overlap_enabled(self) -> bool:
        """Overlapped (bucketed, priority-scheduled, comm-thread)
        gradient reduction — MXNET_KV_OVERLAP (default on), engaged
        only when the store has an actual wire to hide: a
        multi-process collective store, the dist_async parameter
        service, or the synthetic-slow-wire knob.  A single-process
        'local'/'device' store's reduction is a pure no-op — routing
        it through the comm thread would add cross-thread handshakes
        per step for nothing.  See kvstore_sched.py and
        docs/performance.md 'Overlapped collectives'."""
        from ..base import getenv
        kv = self._kvstore
        if kv is None or int(getenv("MXNET_KV_OVERLAP", 1)) == 0:
            return False
        if float(getenv("MXNET_KV_SYNTH_WIRE_GBPS", 0.0)) > 0:
            return True
        ktype = getattr(kv, "type", "")
        if ktype == "dist_async":
            return True
        if ktype in ("ici", "dist", "dist_sync", "dist_device_sync",
                     "dist_sync_device", "horovod"):
            try:
                import jax
                return jax.process_count() > 1
            except Exception:   # noqa: BLE001 - no backend yet
                return False
        return False

    def _push_with_recovery(self, keys, grads, priority=0,
                            reserved_seqs=None) -> None:
        """One kvstore push with the restarted-empty-server recovery
        (shared by the serialized path and the scheduler's per-bucket
        comm-thread dispatch)."""
        kw = {}
        if reserved_seqs is not None:
            kw["_reserved_seqs"] = reserved_seqs
        try:
            self._kvstore.push(keys, grads, priority, **kw)
        except MXNetError as e:
            if not (getattr(self._kvstore, "type", "") == "dist_async"
                    and "uninitialized" in str(e)):
                raise
            # a parameter server restarted with empty state: resume
            # from this worker's current weights (pulled from the
            # server at most one step ago) and re-ship the optimizer.
            # Server-side momentum resets — announce it.
            import warnings
            warnings.warn(
                "parameter server lost its state (restart?) — "
                "re-seeding from this worker's current weights; "
                "server-side optimizer state resets")
            # re-seed the FULL key set _init_kvstore seeds, not just
            # the keys in this push: with ignore_stale_grad, params
            # whose grads are stale right now would otherwise stay
            # uninitialized on the restarted server and re-trigger
            # this recovery (resetting momentum) on every later push
            for i, p in enumerate(self._params):
                if p.grad_req != "null" and p.is_initialized:
                    self._kvstore.init(i, p.data())
            self._kvstore.set_optimizer(self._optimizer)
            self._kvstore.push(keys, grads, priority)

    def allreduce_grads(self, ignore_stale_grad: bool = False) -> None:
        """Sum gradients across data-parallel workers (kvstore push+pull).

        Gradients are fully reduced when this returns — the documented
        allreduce_grads -> inspect/modify grads -> update() pattern
        stays valid under the overlapped scheduler (``step()`` uses
        the internal async variant, where the per-parameter waits move
        into the optimizer update so wire time hides under compute).

        With a sharded SPMD train step this is a no-op: the psum is inside
        the compiled program (kvstore='ici' path, SURVEY.md section 3.5 TPU
        MAPPING)."""
        self._allreduce_grads_async(ignore_stale_grad)
        rnd = getattr(self, "_sched_round", None)
        if rnd is not None:
            # called directly (not via step): honor the public
            # contract — drain the round before handing grads back
            self._sched_round = None
            try:
                streamed = getattr(rnd, "_streaming", False)
                for b in rnd.buckets:
                    rnd.wait(b)
                    if streamed:
                        self._absorb_streamed(b)
            except BaseException:
                rnd.abort()
                raise
            rnd.finish()

    def _allreduce_grads_async(self, ignore_stale_grad: bool = False) \
            -> None:
        """The scheduler-aware reduction ``step()`` drives.

        With MXNET_KV_OVERLAP=1 (default) and a real wire, the
        reduction is bucketed (MXNET_KV_BUCKET_BYTES, composition
        fixed by parameter registration order) and dispatched on the
        scheduler's comm thread in priority order
        (priority=-param_index: the params the next forward needs
        first reduce first); ``self._sched_round`` is left pending and
        the optimizer update for a parameter blocks only on ITS
        bucket, so wire time hides under the remaining
        backward/update compute.  Grads are NOT yet reduced when this
        returns — ``_update`` (or the public wrapper above) consumes
        the round."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._kvstore is None:
            return
        keys, grads = [], []
        for i, p in enumerate(self._params):
            if p.grad_req != "null" and p.is_initialized:
                g = p.data().grad
                if self._update_on_kvstore and \
                        not p.data()._fresh_grad:
                    # same stale-grad contract as the local _update path:
                    # never push (and server-apply) a gradient backward
                    # did not refresh this step
                    if ignore_stale_grad:
                        continue
                    raise MXNetError(
                        f"Gradient of Parameter '{p.name}' has not been "
                        "updated by backward since the last step — wrap "
                        "the forward in autograd.record() or pass "
                        "ignore_stale_grad=True")
                if getattr(g, "stype", "default") == "row_sparse":
                    if self._update_on_kvstore:
                        raise MXNetError(
                            f"Parameter '{p.name}' has a row_sparse "
                            "gradient, which the server-side update "
                            "path does not support — use a dense "
                            "gradient or update_on_kvstore=False")
                    # row-sparse grads skip the dense allreduce round-trip;
                    # multi-worker aggregation uses row_sparse_pull
                    # semantics (reference: Trainer._row_sparse_pull)
                    continue
                keys.append(i)
                grads.append(g)
        if not keys:
            return
        # reference trainer.py semantics: priority = -param_index, so
        # the parameters the next forward consumes first reduce first
        prios = [-i for i in keys]
        if self._overlap_enabled():
            # an armed streaming round (grad-ready hooks fed it during
            # backward) becomes this step's scheduled round; a dirty or
            # mismatched one is discarded and re-reduced fresh
            if not self._update_on_kvstore and \
                    self._consume_stream(keys, grads):
                return
            self._allreduce_scheduled(keys, grads, prios)
            return
        # overlap got disabled between arming and this step: any armed
        # round only ever touched staging — drop it before serializing
        self._discard_stream()
        # serialized path: one batched push (KVStoreICI fuses the small
        # gradients into bucket collectives instead of one per param),
        # then one batched pull — wire time adds to step time
        self._push_with_recovery(keys, grads, prios)
        if self._update_on_kvstore:
            # the store applied the optimizer — pull WEIGHTS back and
            # mark grads consumed; _update is skipped
            ws = [self._params[i].data() for i in keys]
            self._kvstore.pull(keys, out=ws)
            for i in keys:
                self._params[i].data()._fresh_grad = False
        else:
            self._kvstore.pull(keys, out=grads)

    def _allreduce_scheduled(self, keys, grads, prios) -> None:
        """Submit the gradient set to the bucketed comm-thread
        scheduler.  Worker-side-update stores leave the round pending
        for ``_update`` to consume bucket by bucket (the overlap);
        server-side-update stores (dist_async) pull each bucket's
        WEIGHTS back on the comm thread and drain here — bucketed,
        priority-ordered, replay-safe sends, with the per-bucket seqs
        reserved at enqueue."""
        from .. import kvstore_sched as _ks
        kv = self._kvstore
        # a round left over from an aborted step (exception between
        # allreduce and update) must drain before its grad arrays are
        # re-submitted — finish() cancels queued buckets and re-raises
        # any reduce error the aborted step never consumed
        stale = getattr(self, "_sched_round", None)
        if stale is not None:
            self._sched_round = None
            stale.finish()
        if self._update_on_kvstore:
            prepare = None
            if hasattr(kv, "reserve_push_seqs"):
                def prepare(bucket):
                    bucket.ctx["seqs"] = kv.reserve_push_seqs(
                        bucket.keys,
                        [int(v.size) for v in bucket.vals])

            def reduce_fn(bucket):
                self._push_with_recovery(
                    bucket.keys, bucket.vals, bucket.priority,
                    reserved_seqs=bucket.ctx.get("seqs"))
                ws = [self._params[i].data() for i in bucket.keys]
                kv.pull(bucket.keys, out=ws)

            rnd = _ks.submit(keys, grads, prios, reduce_fn,
                             prepare_fn=prepare)
            try:
                for b in rnd.buckets:
                    rnd.wait(b)
                for i in keys:
                    self._params[i].data()._fresh_grad = False
            except BaseException:
                # drain without raising: a secondary bucket error must
                # not mask the one already propagating
                rnd.abort()
                raise
            rnd.finish()
            return

        def reduce_fn(bucket):
            self._push_with_recovery(bucket.keys, bucket.vals,
                                     bucket.priority)
            kv.pull(bucket.keys, out=bucket.vals)

        self._sched_round = _ks.submit(
            keys, grads, prios, reduce_fn,
            strict_order=self._strict_collective_order())

    # -- event-driven streaming (per-layer backward overlap) ----------------
    def _stream_enabled(self) -> bool:
        """The grad-ready streaming path (ISSUE 15): engages exactly
        where the scheduled worker-side path would, minus the cases
        whose contracts it cannot keep — server-side updates apply the
        optimizer AT push (a streamed push is an uncancellable training
        update, so a second backward before step would corrupt it),
        strict-order collective stores need rank-identical dispatch
        sequences (seal order is readiness timing), gradient
        compression mutates per-key error-feedback residuals AT push
        (a dirty round's discarded pushes would leave the residuals
        advanced, and the fallback re-reduction would compress the
        same keys twice in one step — compressed trainers keep the
        step-time submission, where every key compresses exactly
        once), and armed fault plans corrupt gradients at the
        trainer.step site, which must happen BEFORE anything reaches
        the wire."""
        from ..base import getenv
        from .. import faults as _faults
        return (self._overlap_enabled()
                and not self._update_on_kvstore
                and not self._strict_collective_order()
                and not self._compression_params
                and not getattr(self._kvstore, "_compression", None)
                and int(getenv("MXNET_KV_BACKWARD_STREAM", 1)) != 0
                and not _faults._ARMED)

    def _arm_stream(self) -> None:
        """Open next step's streaming round and install the grad-ready
        hooks: backward will ``Round.offer`` each parameter as its
        gradient finalizes, sealing and dispatching reduction buckets
        while the rest of backward still runs.  Re-armed every step —
        cheap (one pass over the params), and it self-heals across
        parameter re-binds, env flips, and fault-plan arming."""
        stale, self._stream_round = self._stream_round, None
        if stale is not None:
            # a skipped/aborted step never consumed its round; sealed
            # buckets only ever reduced into staging, so discarding is
            # free of user-visible effects
            stale.abort()
        self._stream_dirty = False
        if not self._stream_enabled():
            if self._stream_cbs_installed:
                for p in self._params:
                    p.set_grad_ready_cb(None)
                self._stream_cbs_installed = False
            return
        keys, vals, prios = [], [], []
        for i, p in enumerate(self._params):
            if p.grad_req == "null" or not p.is_initialized:
                continue
            if getattr(p, "grad_stype", "default") != "default":
                continue   # row-sparse grads never join dense rounds
            g = p.data().grad
            if g is None or getattr(g, "stype", "default") != "default":
                continue
            keys.append(i)
            vals.append(g)
            prios.append(-i)
        if not keys:
            return
        import jax.numpy as jnp
        staging = self._stream_staging
        for i in keys:
            if i not in staging:
                # a shell for kvstore.pull to rebind — never read until
                # the pull of its bucket landed
                staging[i] = NDArray(jnp.zeros((1,), "float32"),
                                     _wrap=True)
        wself = weakref.ref(self)

        def reduce_fn(bucket):
            tr = wself()
            if tr is None:
                raise MXNetError(
                    "trainer was garbage-collected with a streaming "
                    "gradient-reduction round in flight")
            tr._push_with_recovery(bucket.keys, bucket.vals,
                                   bucket.priority)
            tr._kvstore.pull(
                bucket.keys,
                out=[tr._stream_staging[k] for k in bucket.keys])

        from .. import kvstore_sched as _ks
        self._stream_round = _ks.open_round(keys, vals, prios, reduce_fn)
        self._stream_vals = dict(zip(keys, vals))
        self._stream_bufs = {}

        def make_cb(k):
            def _cb(_arr):
                tr = wself()
                if tr is not None:
                    tr._stream_offer(k)
            return _cb

        keyset = set(keys)
        for i, p in enumerate(self._params):
            p.set_grad_ready_cb(make_cb(i) if i in keyset else None)
        self._stream_cbs_installed = True

    def _stream_offer(self, key: int) -> None:
        """The grad-ready hook body (fires inside backward)."""
        rnd = self._stream_round
        if rnd is None:
            return
        p = self._params[key]
        cur = p._data._grad if p._data is not None else None
        if cur is not self._stream_vals.get(key):
            # the grad wrapper was rebound since arming (a row_sparse
            # cotangent materialized, attach_grad re-ran): the planned
            # value is stale — poison the round, step re-reduces fresh
            self._stream_dirty = True
            return
        if not rnd.offer(key):
            self._stream_dirty = True
            return
        # snapshot the grad's raw buffer: the value that streams is the
        # one backward wrote, and any later rebind (user clipping/
        # scaling between backward and step, zero_grad) must invalidate
        # the round or the modification would be silently discarded
        self._stream_bufs[key] = cur._buf

    def _discard_stream(self) -> None:
        """Drop an armed streaming round (never raising): sealed
        buckets only ever reduced into staging, so there is nothing to
        undo."""
        rnd, self._stream_round = self._stream_round, None
        if rnd is not None:
            rnd.abort()
        self._stream_dirty = False

    def _consume_stream(self, keys, grads) -> bool:
        """At step time: adopt the armed streaming round as this step's
        ``_sched_round`` when it is still sound — otherwise discard it
        (sealed buckets only touched staging) and let the caller run a
        fresh post-backward reduction of the accumulated gradients."""
        rnd, self._stream_round = self._stream_round, None
        if rnd is None:
            return False
        dirty, self._stream_dirty = self._stream_dirty, False
        if dirty or self._update_on_kvstore:
            rnd.abort()
            return False
        actual = set(keys)
        if not actual <= set(rnd.planned_keys):
            rnd.abort()   # a parameter initialized after arming
            return False
        for k, g in zip(keys, grads):
            if self._stream_vals.get(k) is not g:
                rnd.abort()
                return False
            buf = self._stream_bufs.get(k)
            if buf is not None and g._buf is not buf:
                # the grad VALUE was rebound after it streamed (user
                # clipped/scaled it between backward and step): the
                # wire carries the pre-modification value — discard
                # and re-reduce the current gradients
                rnd.abort()
                return False
        rnd.seal_remaining(actual)
        self._sched_round = rnd
        return True

    def _absorb_streamed(self, bucket) -> None:
        """Move one reduced bucket from staging into the user-visible
        grad buffers (called after waiting the bucket): after step, a
        parameter's ``.grad`` holds the reduced gradient exactly as the
        non-streaming paths leave it."""
        with _tracing.child_span("bucket.absorb",
                                 keys=len(bucket.keys)):
            for k, v in zip(bucket.keys, bucket.vals):
                p = self._params[k]
                g = p._data._grad if p._data is not None else None
                s = self._stream_staging.get(k)
                if g is v and s is not None:
                    g._data = s._data

    def _strict_collective_order(self) -> bool:
        """Multi-process collective stores need every rank to issue the
        identical reduction sequence — the scheduler must dispatch in
        pure priority order, never readiness order (readiness timing
        differs per rank and a mismatched collective sequence deadlocks
        the job)."""
        if getattr(self._kvstore, "type", "") not in (
                "ici", "dist", "dist_sync", "dist_device_sync",
                "dist_sync_device", "horovod"):
            return False
        try:
            import jax
            return jax.process_count() > 1
        except Exception:   # noqa: BLE001 - no backend: stay safe
            return True

    def step(self, batch_size: int, ignore_stale_grad: bool = False) -> None:
        """Rescale grads by 1/batch_size and apply one optimizer update."""
        import time
        from .. import faults as _faults
        from .. import metrics as _metrics
        if _faults._ARMED:
            self._fault_site()
        t0 = time.perf_counter()
        try:
            # per-step root span: reduction buckets (seal/dispatch/
            # wire/absorb), PS-side handling, and optimizer updates
            # all land as children in this trace
            with _tracing.span("trainer.step", batch_size=batch_size):
                self._step_impl(batch_size, ignore_stale_grad)
        finally:
            _metrics.TRAINER_STEP_SECONDS.observe(time.perf_counter() - t0)

    def _fault_site(self) -> None:
        """The ``trainer.step`` chaos site: ``kind=nan`` corrupts the
        first fresh gradient BEFORE the reduction/update (and before
        any health-guard check), so the sentry's recovery schedule
        replays deterministically from ``MXNET_FAULT_PLAN``."""
        from .. import faults as _faults
        target = None
        for p in self._params:
            if p.grad_req != "null" and p.is_initialized:
                w = p.data()
                if w.grad is not None and w._fresh_grad:
                    target = w
                    break
        if target is None:
            _faults.maybe_fault("trainer.step")
            return
        out = _faults.maybe_corrupt("trainer.step", [target.grad._data])
        if out[0] is not target.grad._data:
            from ..ndarray.ndarray import from_jax
            target._grad = from_jax(out[0])

    def _step_impl(self, batch_size: int, ignore_stale_grad: bool) -> None:
        self._optimizer.rescale_grad = self._scale / batch_size
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore and \
                hasattr(self._kvstore, "update_optimizer_params"):
            # the worker-side optimizer never runs _update, so advance
            # its schedule clock here or lr_scheduler(num_update) would
            # stay frozen at step 0 forever
            self._optimizer.num_update += 1
            # live hyperparams (lr schedule, loss-scale rescale, wd) must
            # reach the server-side optimizer without resetting its state
            self._kvstore.update_optimizer_params({
                "learning_rate": float(self._optimizer.learning_rate),
                "rescale_grad": float(self._optimizer.rescale_grad),
                "wd": float(self._optimizer.wd)})
        # the async variant: a scheduled round stays pending so
        # _update's per-bucket waits overlap wire with update compute
        self._allreduce_grads_async(ignore_stale_grad)
        if not self._update_on_kvstore:
            self._update(ignore_stale_grad)
        # arm the NEXT step's streaming round: its grad-ready hooks
        # will stream buckets onto the wire during the next backward
        self._arm_stream()

    def update(self, batch_size: int, ignore_stale_grad: bool = False) -> None:
        """Apply the optimizer without gradient reduction (caller already
        reduced, e.g. Horovod-style)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            raise MXNetError(
                "update() cannot be used when updates run on the kvstore "
                "(update_on_kvstore=True) — use step()")
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad: bool = False) -> None:
        updatable = []
        for i, p in enumerate(self._params):
            if p.grad_req == "null" or not p.is_initialized:
                continue
            w = p.data()
            g = w.grad
            if g is None or not w._fresh_grad:
                if ignore_stale_grad:
                    continue
                raise MXNetError(
                    f"Gradient of Parameter `{p.name}` has not been updated "
                    f"by backward since the last step — run backward() "
                    f"inside autograd.record() first, or pass "
                    f"ignore_stale_grad=True")
            if i not in self._states:
                self._states[i] = \
                    self._optimizer.create_state_multi_precision(i, w)
            updatable.append((i, w, g))
        # Both update paths (per-param Optimizer.update and the fused
        # group below) donate weight/state buffers into jitted programs:
        # any pending bulked segment still holding one of those buffers
        # BY VALUE must materialize before the donation deletes it.
        # Targeted (flush_holding, not flush_all): a segment that never
        # captured a donated buffer — the prefetch thread's in-build
        # preprocessing — keeps building.
        import jax as _jax
        from .. import bulk as _bulk
        donated = [w._data for _, w, _ in updatable]
        for i, _, _ in updatable:
            donated.extend(_jax.tree_util.tree_leaves(self._states[i]))
        _bulk.flush_holding(donated, "mutation")
        rnd = getattr(self, "_sched_round", None)
        if rnd is not None:
            # overlapped reduction: walk buckets in registration order
            # (composition IS registration-contiguous), waiting only on
            # the bucket whose parameters update next — the wire for
            # later buckets keeps running under this compute.  Params
            # outside the round (row_sparse grads reduced elsewhere)
            # update in a final chunk.
            self._sched_round = None
            try:
                done = set()
                streamed = getattr(rnd, "_streaming", False)

                def chunk(b):
                    if streamed:
                        # a streamed bucket reduced into staging —
                        # land it in the user-visible grad buffers
                        # before the optimizer reads them
                        self._absorb_streamed(b)
                    members = set(b.keys)
                    done.update(members)
                    self._update_entries(
                        [t for t in updatable if t[0] in members])

                if self._fused_optimizer_ok():
                    # per-param updates are order-independent for
                    # functional optimizers: consume buckets as they
                    # ARRIVE, updating early winners while later
                    # buckets are still on the wire
                    for b in rnd.as_completed():
                        chunk(b)
                else:
                    # order-sensitive optimizers (eager RNG noise in
                    # update, e.g. SGLD) keep registration order so
                    # replays stay deterministic
                    for b in rnd.buckets:
                        rnd.wait(b)
                        chunk(b)
                self._update_entries(
                    [t for t in updatable if t[0] not in done])
            except BaseException:
                # drain without raising: a secondary bucket error must
                # not mask the one already propagating
                rnd.abort()
                raise
            rnd.finish()
        else:
            self._update_entries(updatable)
        for _, w, _ in updatable:
            w._fresh_grad = False

    def _update_entries(self, updatable) -> None:
        """Apply the optimizer to one list of (idx, weight, grad)
        entries — the fused-group batching below is unchanged from the
        pre-scheduler path, it just runs per bucket now."""
        if not updatable:
            return
        with _tracing.child_span("optimizer.update",
                                 params=len(updatable)):
            self._update_entries_impl(updatable)

    def _update_entries_impl(self, updatable) -> None:
        agg = self._optimizer.aggregate_num
        if len(updatable) > 1 and agg > 1 and self._fused_optimizer_ok():
            # reference semantics: MXNET_OPTIMIZER_AGGREGATION_SIZE bounds
            # the number of parameters per fused update batch. Params that
            # can't fuse (row_sparse grads, fp32 master weights) take the
            # per-param path WITHOUT disabling fusion for the dense
            # majority in mixed models.
            fusible, rest = [], []
            for t in updatable:
                (fusible if self._param_fusible(t) else rest).append(t)
            if len(fusible) < 2:
                fusible, rest = [], updatable
            for k in range(0, len(fusible), agg):
                group = fusible[k:k + agg]
                if len(group) > 1:
                    self._fused_update(group)
                else:
                    i, w, g = group[0]
                    self._states[i] = \
                        self._optimizer.update_multi_precision(
                            i, w, g, self._states[i])
        else:
            rest = updatable
        for i, w, g in rest:
            self._states[i] = self._optimizer.update_multi_precision(
                i, w, g, self._states[i])

    def _fused_optimizer_ok(self) -> bool:
        """Optimizers fully described by the functional ``_step`` core can
        fuse; ones that override ``update``/``update_multi_precision``
        (e.g. SGLD's eager Langevin noise) must take the per-param path."""
        cls = type(self._optimizer)
        return not (cls._step is opt.Optimizer._step or
                    cls.update is not opt.Optimizer.update or
                    cls.update_multi_precision is not
                    opt.Optimizer.update_multi_precision)

    def _param_fusible(self, t) -> bool:
        """Dense params without fp32-master-weight state can join a fused
        update group."""
        i, w, g = t
        return (getattr(g, "stype", "default") != "row_sparse" and
                not isinstance(self._states[i], opt.MasterWeightState))

    _HYPER_CACHE_CAP = 512

    def _committed_hypers(self, lrs, wds, rescale, clip):
        """Value-keyed LRU of device hyperparameter arrays.

        Building fresh ``jnp.asarray`` arrays for lr/wd/rescale/clip
        EVERY step is four host->device transfers per update (the same
        cost ``SPMDTrainer._committed_scalar`` avoids).  Hyperparameters
        revisit a small value set (constant, or a cyclic schedule), so
        an LRU by value makes the steady state zero-transfer."""
        import jax.numpy as jnp
        key = (tuple(lrs), tuple(wds), float(rescale), float(clip))
        cache = getattr(self, "_hyper_cache", None)
        if cache is None:
            from collections import OrderedDict
            cache = self._hyper_cache = OrderedDict()
        hit = cache.get(key)
        if hit is None:
            hit = (jnp.asarray(lrs, jnp.float32),
                   jnp.asarray(wds, jnp.float32),
                   jnp.float32(rescale), jnp.float32(clip))
            cache[key] = hit
            if len(cache) > self._HYPER_CACHE_CAP:
                cache.popitem(last=False)
        else:
            cache.move_to_end(key)
        return hit

    def _fused_ts(self, key, ts):
        """Device-resident per-group schedule clock.  The counts
        increment every step, so a host-built array would never cache —
        instead the fused program returns ``ts + 1`` and the device copy
        advances in-program; the host-side expected-value check resyncs
        after ``load_states``/rewind (and a skipped update, which never
        calls this, leaves both sides untouched)."""
        import jax.numpy as jnp
        expected = tuple(float(t) for t in ts)
        clock = getattr(self, "_fused_clock", None)
        if clock is None:
            clock = self._fused_clock = {}
        hit = clock.get(key)
        if hit is not None and hit[1] == expected:
            return hit[0]
        return jnp.asarray(ts, jnp.float32)

    def _fused_update(self, group) -> None:
        """One compiled program applying a group of parameter updates —
        the TPU-native form of the reference's multi-tensor ops
        (``multi_sgd_mom_update`` etc.): XLA fuses the group's update
        sweep into one dispatch."""
        import jax
        import jax.numpy as jnp
        o = self._optimizer
        cls = type(o)
        lrs, wds, ts = [], [], []
        for i, w, g in group:
            o._update_count(i)
            lrs.append(o._get_lr(i))
            wds.append(o._get_wd(i))
            ts.append(o._index_update_count[i])
        key = (cls, o.clip_gradient is not None,
               tuple((i, tuple(w.shape), str(w.dtype), o._hyper(i))
                     for i, w, _ in group))
        cache = getattr(self, "_fused_cache", None)
        if cache is None:
            cache = self._fused_cache = {}
        fn = cache.get(key)
        if fn is None:
            has_clip = o.clip_gradient is not None
            hps = [o._hyper(i) for i, _, _ in group]

            def raw(ws, gs, sts, lrs_, wds_, ts_, rescale_, clip_):
                new_ws, new_sts = [], []
                for k, (w, g, st) in enumerate(zip(ws, gs, sts)):
                    g = g.astype(jnp.float32) if w.dtype != g.dtype else g
                    g = g * rescale_
                    if has_clip:
                        g = jnp.clip(g, -clip_, clip_)
                    nw, ns = cls._step(w, g, st, lrs_[k], wds_[k], ts_[k],
                                       hps[k])
                    new_ws.append(nw)
                    new_sts.append(ns)
                # the schedule clock advances IN-PROGRAM (fed back as
                # the next step's ts_): the loop never ships a fresh
                # varying-value host array per step
                return new_ws, new_sts, ts_ + 1.0

            fn = cache[key] = jax.jit(raw, donate_argnums=(0, 2, 5))
        clip = o.clip_gradient if o.clip_gradient is not None else 0.0
        lrs_a, wds_a, rescale_a, clip_a = self._committed_hypers(
            lrs, wds, o.rescale_grad, clip)
        new_ws, new_sts, ts_next = fn(
            [w._data for _, w, _ in group],
            [g._data for _, _, g in group],
            [self._states[i] for i, _, _ in group],
            lrs_a, wds_a, self._fused_ts(key, ts), rescale_a, clip_a)
        self._fused_clock[key] = (
            ts_next, tuple(float(t) + 1.0 for t in ts))
        from .. import engine
        for (i, w, _), nw, ns in zip(group, new_ws, new_sts):
            w._data = nw
            engine.track(nw)
            self._states[i] = ns

    def zero_grad(self) -> None:
        for p in self._params:
            p.zero_grad()

    # -- exact resume (reference: Trainer.save_states/load_states) ----------
    def save_states(self, fname: str) -> None:
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore and self._kvstore is not None:
            if hasattr(self._kvstore, "save_optimizer_states"):
                # states live in the store (server-side for dist_async) —
                # the reference delegates in exactly this mode
                self._kvstore.save_optimizer_states(fname)
                return
        import numpy as _np
        import jax
        payload = {
            "format": 2,  # >=2: MasterWeightState pickles as its type
            "num_update": self._optimizer.num_update,
            "index_update_count": self._optimizer._index_update_count,
            "states": {
                i: jax.tree_util.tree_map(lambda a: _np.asarray(a), s)
                for i, s in self._states.items()},
        }
        with open(fname, "wb") as f:
            pickle.dump(payload, f)

    def load_states(self, fname: str) -> None:
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore and self._kvstore is not None:
            if hasattr(self._kvstore, "load_optimizer_states"):
                self._kvstore.load_optimizer_states(fname)
                return
        import jax.numpy as jnp
        import jax
        import numpy as _np
        with open(fname, "rb") as f:
            payload = pickle.load(f)
        self._optimizer.num_update = payload["num_update"]
        self._optimizer._index_update_count = payload["index_update_count"]

        legacy = payload.get("format", 1) < 2

        def restore(i, s):
            # format<2 states stored the master-weight layout as a plain
            # (master, inner_state_tuple) tuple; rewrap so the typed
            # dispatch still routes them. The inner-is-a-tuple condition
            # distinguishes it from Adam-style (m, v) plain state (whose
            # second element is an array), and masters only ever exist
            # for non-fp32 weights.
            if legacy and self._optimizer.multi_precision and \
                    type(s) is tuple and len(s) == 2 and \
                    isinstance(s[0], _np.ndarray) and \
                    s[0].dtype == _np.float32 and \
                    isinstance(s[1], tuple) and \
                    i < len(self._params) and \
                    self._params[i].dtype != _np.float32 and \
                    tuple(s[0].shape) == tuple(self._params[i].shape):
                s = opt.MasterWeightState(s[0], s[1])
            return jax.tree_util.tree_map(jnp.asarray, s)

        self._states = {i: restore(i, s)
                        for i, s in payload["states"].items()}
