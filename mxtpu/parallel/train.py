"""ShardedTrainStep: the whole training step as ONE compiled sharded program.

This is the TPU-native fast path that replaces the reference's entire
per-batch machinery — DataParallelExecutorGroup batch slicing
(python/mxnet/module/executor_group.py:281), KVStore push/pull gradient
reduction (src/kvstore/kvstore_local.h:184), and in-engine optimizer kernels
(src/operator/optimizer_op.cc) — with a single ``jax.jit`` over a
`jax.sharding.Mesh`:

* forward + loss + backward + optimizer update trace into one XLA program,
* the batch is sharded on the ``data`` axis; the mean loss / summed gradients
  ARE the cross-device all-reduce (GSPMD inserts the collectives — the
  explicit push/pull of the reference becomes implicit dataflow),
* parameters may carry PartitionSpecs (tensor parallelism — absent from the
  reference, SURVEY §2.3) and are donated, so the update is in-place in HBM
  like the reference's in-engine mutate-in-place optimizer ops.

The block's imperative forward is traced through the same `_TraceFrame`
machinery as CachedOp (mxtpu/gluon/block.py), so BatchNorm moving-stat
updates and Dropout RNG stay functional under the trace.

Since ISSUE 7 this class is a thin wrapper over machinery shared with the
mesh-native ``gluon.Trainer``: the optimizer update rules come from the
``mxtpu.optimizer_fused`` registry (full zoo, traced-t hyper twins, one
multi-precision storage rule), and the ZeRO-1 state-sharding plan mirrors
``optimizer_fused.MeshPlan`` — the difference is only WHERE backward
lives (inside this one jit vs the eager autograd tape).
"""
from __future__ import annotations

import re
import weakref

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import optimizer as opt_mod
from .. import optimizer_fused as _fused
from .. import random as _random
from .. import telemetry
from ..base import MXNetError
from ..gluon.block import _flatten_nd, _regroup, _run_traced
from ..ndarray import NDArray
from ..optimizer_fused import _tree_data

__all__ = ["ShardedTrainStep", "pure_forward"]




def pure_forward(block, train=False):
    """Extract the block's forward as a pure jittable function.

    Returns ``(fn, param_datas)`` where ``fn(param_datas, *input_arrays,
    rng=None)`` maps raw jax arrays to raw jax array(s). For stochastic
    layers (Dropout) in ``train=True`` mode, each ``rng=None`` call draws a
    fresh key from ``mxtpu.random`` — two calls produce DIFFERENT dropout
    masks, matching eager semantics (a fixed default key would silently
    reuse one mask forever). Under an outer ``jax.jit`` the draw happens at
    trace time and is baked into the executable: pass ``rng=`` explicitly
    per call there. ``train=False`` keeps a fixed key — deterministic
    inference needs no entropy. The block must be initialized with shapes
    settled (run one eager forward first for deferred init).
    """
    params = list(block.collect_params().values())
    if any(p._data is None for p in params):
        raise MXNetError(
            "pure_forward requires initialized parameters; call initialize() "
            "and run one forward pass to settle deferred shapes")
    param_datas = [p.data()._data for p in params]

    def fn(param_datas, *in_datas, rng=None):
        if rng is None:
            key = _random.next_key() if train else jax.random.PRNGKey(0)
        else:
            key = rng

        def body():
            return block(*[NDArray(d) for d in in_datas])
        out, _aux = _run_traced(params, param_datas, key, train, body)
        flat = _flatten_nd(out, [])
        datas = [o._data for o in flat]
        return datas[0] if len(datas) == 1 else tuple(datas)

    return fn, param_datas


class ShardedTrainStep:
    """One jitted, mesh-sharded training step for a gluon block.

    Parameters
    ----------
    block : HybridBlock — initialized, shapes settled.
    loss : callable ``loss(out, label) -> NDArray`` (e.g. a gluon Loss).
    mesh : jax.sharding.Mesh with a data axis (and optionally model/sp axes).
    optimizer : registry name (or Optimizer instance) with a traced-t
        functional rule in the ``mxtpu.optimizer_fused`` registry — the
        whole zoo (sgd/adam/rmsprop/adagrad/adadelta/ftrl/adamax/nag/
        signum/ftml/dcasgd/groupadagrad, ``optimizer_fused.
        traced_rule_names()``), ONE registry shared with the fused Trainer
        step so the two jit surfaces cannot drift. Host-state optimizers
        (Nadam's m_schedule, SGLD's rng, LBSGD's norms) have no pure rule
        and raise — use the eager ``gluon.Trainer`` for those.
    optimizer_params : dict — learning_rate, momentum, wd, clip_gradient,
        betas... (python-side; a changed learning rate does NOT retrigger
        compilation: hyperparams are traced scalars).
    data_axis : mesh axis name the batch is sharded over.
    param_specs : list of ``(name_regex, PartitionSpec)`` — tensor-parallel
        placement rules; first match wins; default replicated. Shapes not
        divisible by the mesh axis fall back to replicated.
    batch_specs : optional list of PartitionSpecs, one per flattened batch
        input; default shards dim 0 over `data_axis`.
    forward : optional ``forward(block, *batch) -> loss NDArray`` overriding
        the default ``loss(block(data), label)`` convention.
    shard_weight_update : bool — ZeRO-1 cross-replica weight-update sharding
        (arXiv:2004.13336): optimizer state of REPLICATED trainable params
        whose dim 0 divides the data-axis size is sharded over that axis
        (reduce-scatter grad -> shard-local update -> all-gather weight,
        bit-identical loss, state memory / replica count). Params that are
        tensor-parallel or not divisible silently keep replicated state.
    """

    def __init__(self, block, loss, mesh, optimizer="sgd",
                 optimizer_params=None, data_axis="data", param_specs=(),
                 batch_specs=None, forward=None, donate=True,
                 shard_weight_update=False):
        # one ``train_step.init`` trace (mxtpu/telemetry.py) with the
        # children ``.place_params`` / ``.create_states`` / ``.place_states``:
        # where a restart's seconds go before the first step. What the
        # children leave of the root is the rule lookup and the sharding
        # plans
        with telemetry.span("train_step.init", cat="setup", new_trace=True):
            self._init(block, loss, mesh, optimizer, optimizer_params,
                       data_axis, param_specs, batch_specs, forward, donate,
                       shard_weight_update)

    def _init(self, block, loss, mesh, optimizer, optimizer_params,
              data_axis, param_specs, batch_specs, forward, donate,
              shard_weight_update):
        self._block = block
        self._loss = loss
        self._mesh = mesh
        self._data_axis = data_axis
        self._forward = forward
        self._donate = donate
        self._batch_specs = batch_specs

        opt_params = dict(optimizer_params or {})
        self._lr_scheduler = opt_params.pop("lr_scheduler", None)
        if isinstance(optimizer, opt_mod.Optimizer):
            if opt_params:
                raise MXNetError("optimizer_params must be empty when "
                                 "optimizer is an Optimizer instance")
            opt = optimizer
        else:
            try:
                opt = opt_mod.create(optimizer, **opt_params)
            except TypeError as e:
                raise MXNetError("unknown optimizer_params for %r: %s"
                                 % (optimizer, e))
        # ONE functional-rule registry for both jit surfaces (ISSUE 7
        # satellite): the fused Trainer step and this sharded step draw the
        # same static/step/thyper triple, so the zoo and the multi-precision
        # storage rule cannot fork between them
        rule = _fused.functional_rule(opt)
        if rule is None or rule.thyper is None:
            raise MXNetError(
                "ShardedTrainStep needs a pure traced-t update rule from "
                "the mxtpu.optimizer_fused registry; %r has none "
                "(supported: %s). Host-state optimizers (Nadam/SGLD/LBSGD) "
                "keep their eager semantics on the gluon.Trainer path."
                % (optimizer, _fused.traced_rule_names()))
        if getattr(opt, "multi_precision", False):
            raise MXNetError(
                "ShardedTrainStep's in-jit update does not implement the "
                "multi-precision (f32-master) storage rule — its states "
                "would be (master, base) tuples the shared rule cannot "
                "consume. Use the mesh-native gluon.Trainer, whose "
                "FusedUpdater handles multi_precision sharded.")
        self._opt = opt
        self._rule = rule
        self._static = rule.static(opt)
        self._wd = float(opt.wd)
        self._num_update = 0

        params = list(block.collect_params().values())
        if any(p._data is None for p in params):
            raise MXNetError(
                "initialize() the block and run one forward pass before "
                "building a ShardedTrainStep")
        self._params = params
        self._trainable = [p.grad_req != "null" for p in params]

        # a mesh spanning several processes (multi-host DCN training) needs
        # global-array assembly instead of plain device_put — each process
        # contributes its addressable shards (the reference's ps-lite
        # worker/server split becomes this one symmetric path)
        self._multiprocess = len(
            {d.process_index for d in mesh.devices.flat}) > 1

        rules = [(re.compile(pat), spec) for pat, spec in param_specs]
        self._param_shardings = [
            NamedSharding(mesh, self._spec_for(p, rules)) for p in params]
        with telemetry.span("train_step.init.place_params", cat="setup"):
            self._param_datas = [
                self._place(p.data()._data, s)
                for p, s in zip(params, self._param_shardings)]
            for p, d in zip(params, self._param_datas):
                p.data()._set_data(d)
        # optimizer state in the RULE's structure (None | array | tuple —
        # exactly what the optimizer's create_state builds and the shared
        # step fn consumes), materialized up front and placed on the mesh
        with telemetry.span("train_step.init.create_states", cat="setup"):
            raw_states = [
                _tree_data(self._opt.create_state_multi_precision(
                    i, NDArray(d))) if t else None
                for i, (d, t) in enumerate(zip(self._param_datas,
                                               self._trainable))]

        # ZeRO-1 / cross-replica weight-update sharding (Xu et al. 2020,
        # arXiv:2004.13336 — PAPERS.md): optimizer state of replicated
        # params is sharded over the data axis; GSPMD then lowers the
        # update to reduce-scatter(grad) -> shard-local update ->
        # all-gather(weight), cutting state memory and update FLOPs by the
        # replica count with bit-identical results (tests/test_parallel.py
        # asserts the loss trajectory matches the replicated run).
        def _state_sharding(p_sh, d, st):
            if not shard_weight_update:
                return p_sh
            ax = mesh.shape.get(data_axis, 1)
            leaves = jax.tree_util.tree_leaves(st)
            if (p_sh.is_fully_replicated and ax > 1 and d.ndim >= 1
                    and d.shape and d.shape[0] % ax == 0
                    and all(l.ndim >= 1 and l.shape
                            and l.shape[0] % ax == 0 for l in leaves)):
                return NamedSharding(mesh, P(data_axis))
            return p_sh

        state_plans = [
            _state_sharding(sh, d, st)
            for d, st, sh in zip(self._param_datas, raw_states,
                                 self._param_shardings)]
        with telemetry.span("train_step.init.place_states", cat="setup"):
            self._opt_states = [
                jax.tree_util.tree_map(
                    lambda s, _pl=plan: self._place(s, _pl), st)
                for st, plan in zip(raw_states, state_plans)]
        self._state_shardings = [
            jax.tree_util.tree_map(lambda _s, _pl=plan: _pl, st)
            for st, plan in zip(raw_states, state_plans)]
        self._jit = None
        self._in_fmt = None
        self._in_sig = None
        self._policy = None

    # ------------------------------------------------------------- placement
    def _place(self, data, sharding, local=False):
        """Put a host value onto the mesh. Single-process: device_put.

        Multi-process, ``local=False`` (parameters / optimizer state): every
        process holds the same FULL value and each contributes the shards it
        addresses — correct for replicated and tensor-parallel specs alike.
        ``local=True`` (batch inputs): the value is this process's local
        shard and the global batch is their concatenation (standard SPMD
        per-host data loading).
        """
        if not self._multiprocess:
            return jax.device_put(data, sharding)
        import numpy as np
        from jax.experimental import multihost_utils
        arr = np.asarray(data)
        if local:
            return multihost_utils.host_local_array_to_global_array(
                arr, self._mesh, sharding.spec)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])

    def _spec_for(self, param, rules):
        for pat, spec in rules:
            if pat.match(param.name):
                spec = spec if isinstance(spec, P) else P(*spec)
                # replicated fallback when the shape doesn't divide the mesh
                ok = True
                for dim, axis in zip(param.shape, tuple(spec)):
                    if axis is None:
                        continue
                    axes = axis if isinstance(axis, tuple) else (axis,)
                    size = 1
                    for a in axes:
                        if a not in self._mesh.shape:
                            raise MXNetError(
                                "param_specs rule %r -> %s names axis %r not "
                                "in mesh axes %s"
                                % (pat.pattern, spec, a,
                                   tuple(self._mesh.shape)))
                        size *= self._mesh.shape[a]
                    if dim % size:
                        ok = False
                if ok:
                    return spec
                return P()
        return P()

    # ------------------------------------------------------------------ step
    def _resolve_in_shardings(self, n_inputs):
        """Batch input shardings (needed for placement BEFORE the build,
        so the compile service can AOT-lower against placed example
        args)."""
        mesh = self._mesh
        if self._batch_specs is not None:
            in_specs = [spec if isinstance(spec, P) else P(*spec)
                        for spec in self._batch_specs]
        else:
            in_specs = [P(self._data_axis)] * n_inputs
        self._in_shardings = [NamedSharding(mesh, s) for s in in_specs]

    def _jitted(self, in_fmt, n_inputs):
        """The whole step as a plain ``jax.jit`` over this instance's
        shardings: what :meth:`_build` hands the compile service to lower
        and compile, and what :meth:`lowered` lowers anew."""
        trainable = self._trainable
        loss_blk, forward = self._loss, self._forward
        rule, static = self._rule, self._static
        # the jitted step outlives this instance (the compile service's
        # store and the executable ledger keep it), so it reaches the block
        # and its parameters through a weak reference: held strongly, their
        # arrays and gradient buffers would never be freed. It is traced
        # only by this instance's own calls, so the reference is live then
        me = weakref.ref(self)
        thyper = rule.thyper
        t_idx = [i for i, t in enumerate(trainable) if t]

        wd = self._wd  # static: `if wd:` in the kernels

        # the jitted function's name is the compiled module's in a device
        # trace (``jit_sharded_train_step_<hash>``), and the two scopes
        # below are on every operation's metadata: ``forward`` (its
        # transpose, the backward, reads ``transpose(jvp(forward))``) and
        # ``optimizer``. Names only: no operation changes
        def sharded_train_step(param_datas, opt_states, hyper, rng, in_datas):
            lr, t = hyper  # traced scalars: lr schedule / step count don't recompile
            params, block = me()._params, me()._block
            frozen = list(param_datas)

            def loss_of(train_datas):
                datas = list(frozen)
                for i, d in zip(t_idx, train_datas):
                    datas[i] = d

                def body():
                    args, _, _ = _regroup(
                        [NDArray(d) for d in in_datas], in_fmt)
                    if forward is not None:
                        return forward(block, *args)
                    if len(args) < 2:
                        raise MXNetError(
                            "default convention needs (data..., label); pass "
                            "forward= for custom batch structures")
                    out = block(*args[:-1])
                    return loss_blk(out, args[-1])

                with jax.named_scope("forward"):
                    out, aux = _run_traced(params, datas, rng, True, body)
                    scalar = jnp.mean(out._data)
                return scalar, aux

            train_datas = [param_datas[i] for i in t_idx]
            (loss_val, aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(train_datas)

            new_datas = list(param_datas)
            new_states = list(opt_states)
            # the shared registry's traced-t hyper twin (optimizer_fused
            # thyper): bias-correction terms are built IN-GRAPH from the
            # traced (lr, wd, t), so schedules and step count never
            # recompile — same tuples the fused Trainer step traces
            with jax.named_scope("optimizer"):
                h = thyper(static, lr, wd, t)
                for j, i in enumerate(t_idx):
                    w, st = rule.step(new_datas[i], grads[j], opt_states[i],
                                      h, 1.0, static)
                    # the f32 lr/state promote the arithmetic to f32
                    # (precision), but storage keeps the parameter dtype
                    # (bf16 fast path) — the reference's multi-precision
                    # update pattern (optimizer.py:500 mp_sgd_update),
                    # shared with FusedUpdater
                    new_datas[i] = w.astype(param_datas[i].dtype)
                    new_states[i] = jax.tree_util.tree_map(
                        lambda n, o: n.astype(o.dtype), st, opt_states[i])
                for i, a in enumerate(aux):
                    if a is not None:  # BatchNorm moving stats etc.
                        new_datas[i] = a.astype(new_datas[i].dtype)
            return new_datas, new_states, loss_val

        repl = NamedSharding(self._mesh, P())
        self._resolve_in_shardings(n_inputs)
        return jax.jit(
            sharded_train_step,
            in_shardings=(self._param_shardings,
                          list(self._state_shardings),
                          None, None, self._in_shardings),
            out_shardings=(self._param_shardings,
                           list(self._state_shardings),
                           repl),
            donate_argnums=(0, 1) if self._donate else ())

    def _build(self, in_fmt, n_inputs, example_args=None):
        """Resolve the step through the compile service, ahead of time:
        with placed example arguments the service traces, lowers and
        compiles ONCE (what a plain jit's first call does) and hands back
        that ``Compiled`` (the executable ledger keeps it with its
        operation table, ``xprof.step_operations``); without them (a step
        called under an outer trace) a plain jit, as before."""
        from .. import compile_service as csvc
        from ..ops.registry import policy_key
        # retrace watchdog: one compile per batch structure — after the
        # first step this site must stay flat (an in_fmt change means the
        # caller reshaped its batch pytree mid-run); the service reports
        # the miss with the finished executable riding compiled= into the
        # xprof ledger
        retrace_prov = {
            "block": type(self._block).__name__, "n_inputs": n_inputs,
            "donate": bool(self._donate),
            "policy_key": list(policy_key())}
        loss_blk, forward = self._loss, self._forward
        in_shapes = None
        if example_args is not None:
            in_shapes = tuple((tuple(d.shape), str(d.dtype))
                              for d in example_args[4])
        key = csvc.canonical_key(
            site="parallel.train_step",
            fn_id="train_step:%s:%s:%s:%s" % (
                type(self._block).__name__,
                csvc.source_token(type(self._block)),
                csvc.source_token(loss_blk) if loss_blk is not None
                else "-",
                csvc.source_token(forward) if forward is not None
                else "-"),
            signature=(tuple(in_fmt), n_inputs, in_shapes,
                       repr(self._static), type(self._opt).__name__,
                       tuple(self._trainable),
                       self._wd,
                       tuple((tuple(d.shape), str(d.dtype))
                             for d in self._param_datas)),
            policy=policy_key(),
            # per-buffer sharding tokens: a TP layout and a DP layout of
            # the same shapes are DIFFERENT executables (in/out
            # shardings are compiled in)
            sharding=(self._plan_fingerprint(),
                      tuple(str(s) for s in self._param_shardings),
                      tuple(repr(jax.tree_util.tree_map(str, s))
                            for s in self._state_shardings)),
            donation=(0, 1) if self._donate else (),
            device=csvc.device_token(mesh=self._mesh),
            nonce=csvc.instance_nonce(self))
        entry = csvc.get_or_build(
            key, lambda: self._jitted(in_fmt, n_inputs),
            provenance=retrace_prov,
            example_args=csvc.concrete_args(example_args)
            if example_args is not None else None, aot=True)
        return entry.fn

    def _plan_fingerprint(self):
        """Mesh layout token for the cache key: shape, axis names, and
        the batch specs that drive the input shardings."""
        return (tuple(self._mesh.shape.items()), self._data_axis,
                repr(self._batch_specs), bool(self._donate))

    def __call__(self, *batch):
        """Run one step on a batch (``(data, label)`` by default). Returns the
        scalar loss as a lazy NDArray — no host sync (SURVEY §1: frontend
        never blocks; sync at asnumpy()).

        Each call is one ``train_step`` trace (mxtpu/telemetry.py), the
        root a profiler step numbered by the update it makes, with the
        children ``train_step.place`` / ``.rng`` / ``.build`` (rebuilds
        only) or ``.launch`` / ``.commit``: host timers, no device work."""
        with telemetry.span("train_step", d2h=True, new_trace=True,
                            step=self._num_update + 1):
            return self._step(batch)

    def _step(self, batch):
        with telemetry.span("train_step.place"):
            in_fmt = []
            flat = _flatten_nd(batch, in_fmt)
            in_datas = [x._data if isinstance(x, NDArray) else jnp.asarray(x)
                        for x in flat]
            # rebuild on a policy flip too: the traced block consults the
            # registry.policy_key levers (BN one-pass, conv routing, ...)
            # at trace time — reusing the old executable would silently run
            # the stale policy (the aliasing hazard documented at
            # registry.py:90)
            from ..ops.registry import policy_key
            policy = policy_key()
            # input shapes join the rebuild condition: the compile service
            # may hand back a shape-pinned AOT executable (disk-warm start),
            # and a changed signature is a real compile either way — a
            # repeated signature is a service hit, not a retrace
            in_sig = tuple((tuple(d.shape), str(d.dtype)) for d in in_datas)
            rebuild = self._jit is None or self._in_fmt != in_fmt \
                or self._policy != policy or self._in_sig != in_sig
            prev_shardings = getattr(self, "_in_shardings", None)
            if rebuild:
                self._resolve_in_shardings(len(in_datas))
            in_datas = [self._place(d, s, local=True)
                        for d, s in zip(in_datas, self._in_shardings)]
            self._num_update += 1
            lr = (self._lr_scheduler(self._num_update)
                  if self._lr_scheduler else float(self._opt.learning_rate))
            hyper = (jnp.float32(lr), jnp.float32(self._num_update))
        with telemetry.span("train_step.rng"):
            rng = _random.next_key()
        args = (self._param_datas, self._opt_states, hyper, rng, in_datas)
        if rebuild:
            # built AFTER placement so the service can AOT-lower (and
            # persist) against the real placed argument signature; the
            # rebuild-condition state (incl. the input shardings the
            # placement consumed) commits only on SUCCESS — a transient
            # build failure must not leave a stale-policy executable or
            # mismatched shardings looking current on the next step.
            # The span covers the first call of what was built too. The
            # service builds ahead of time: the one Python trace, lowering
            # and compile of the step lie in the build, and the first call
            # runs the ``Compiled`` it will run ever after
            with telemetry.span("train_step.build"):
                try:
                    self._jit = self._build(in_fmt, len(in_datas),
                                            example_args=args)
                except BaseException:
                    self._in_shardings = prev_shardings
                    raise
                self._in_fmt = in_fmt
                self._policy = policy
                self._in_sig = in_sig
                new_datas, new_states, loss = self._jit(*args)
        else:
            with telemetry.span("train_step.launch"):
                new_datas, new_states, loss = self._jit(*args)
        with telemetry.span("train_step.commit"):
            self._param_datas = new_datas
            self._opt_states = new_states
            for p, d in zip(self._params, new_datas):
                p.data()._set_data(d)
            # the last references to the step's donated inputs (several
            # hundred arrays): dropped here and not at the return, so
            # that what freeing them costs is inside this span
            del args, in_datas
            return NDArray(loss)

    def optimizer_states(self):
        """The optimizer state of each trainable parameter, in parameter
        order, in the rule's own structure (an array, a tuple of arrays,
        or None for a stateless rule). The arrays are the live ones: the
        next step donates them, so copy what has to outlast it."""
        return [st for st, t in zip(self._opt_states, self._trainable) if t]

    def compiled(self):
        """The step's compiled executable, the very one that runs
        (``as_text()``, ``cost_analysis()``, ``memory_analysis()``): the
        handle the compile service built ahead of time in the first call's
        ``train_step.build``. Nothing is traced, lowered or compiled here.
        Requires at least one __call__."""
        if self._jit is None:
            raise MXNetError("run at least one step before asking for the "
                             "compiled step")
        if not hasattr(self._jit, "cost_analysis"):
            raise MXNetError("this step was built under an outer trace and "
                             "runs a plain jit: it holds no executable")
        return self._jit

    def lowered(self):
        """The step traced and lowered ANEW at the signature it last ran
        at (``as_text()`` is its StableHLO, ``as_text(debug_info=True)``
        with the names): one more Python trace and lowering, for tools and
        tests that compare programs. The step's own path never calls it.
        Requires at least one __call__."""
        args = jax.tree_util.tree_map(
            lambda info: jax.ShapeDtypeStruct(info.shape, info.dtype),
            self.compiled().args_info[0])
        return self._jitted(self._in_fmt, len(self._in_sig)).lower(*args)

    def compiled_step_flops(self):
        """FLOPs of one compiled step per XLA's own cost model.

        The analog of the reference's per-op FLOP counting in its benchmark
        scripts — but measured on the exact fused HLO that runs, not a
        hand-derived formula. Requires at least one __call__ (shapes must be
        known); compiles nothing.
        """
        from .. import perf_model
        flops = perf_model.flops_of(self.compiled())
        if flops is None:
            raise MXNetError(
                "XLA cost analysis exposes no flops for this "
                "executable on this backend/jax version")
        return flops

    @property
    def learning_rate(self):
        if self._lr_scheduler is not None:
            return self._lr_scheduler(max(self._num_update, 1))
        return float(self._opt.learning_rate)

    def set_learning_rate(self, lr):
        if self._lr_scheduler is not None:
            # the reference Trainer raises here too (gluon/trainer.py)
            raise MXNetError(
                "cannot set learning_rate: an lr_scheduler is active")
        self._opt.set_learning_rate(float(lr))
