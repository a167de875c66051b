"""The jitted SPMD training step.

One ``jax.jit(shard_map(...))`` program per configuration replaces the
reference's entire per-step dataflow — imperative forward/backward through
the dependency engine, engine-async kvstore push, PS-side merge at two
tiers, optimizer at the global server, and the pull back down
(SURVEY.md §3.2-3.4).  XLA sees compute and both collective tiers in one
graph and overlaps them (the latency-hiding the reference needed P3 and
engine threads for comes from the scheduler here).
"""

from __future__ import annotations

import contextlib
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from geomx_tpu.parallel.collectives import shard_map_compat
from geomx_tpu.sync.base import SyncAlgorithm
from geomx_tpu.telemetry import probes as _probes
from geomx_tpu.topology import DC_AXIS, SP_AXIS, WORKER_AXIS, HiPSTopology
from geomx_tpu.train.state import TrainState, state_specs
from geomx_tpu.utils.profiler import profile_scope


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def _norm_input(x: jax.Array) -> jax.Array:
    """Image inputs (uint8 or float, 0-255 scale) normalize to [0,1]
    on-device, preserving the historical convention for float-array
    callers; WIDE integer dtypes are token ids and pass through
    untouched (embeddings index them directly)."""
    if jnp.issubdtype(x.dtype, jnp.integer) and x.dtype != jnp.uint8:
        return x
    return x.astype(jnp.float32) / 255.0


def resolve_precision(config=None) -> str:
    """The compute precision for this build: ``"fp32"`` or ``"bf16"``.

    Static, resolved at build time like every other step-shaping knob
    (``GeoConfig(precision=...)`` wins; ``GEOMX_PRECISION`` covers
    config-less call sites).  bf16 means fp32 master weights with bf16
    activations/matmuls — the loss, the gradients and the optimizer
    state all stay fp32, which is why no loss scaling exists anywhere
    in this mode: nothing that accumulates ever leaves fp32, and bf16
    shares fp32's exponent range so activations cannot underflow the
    way fp16 activations do (docs/performance.md)."""
    if config is not None:
        raw = getattr(config, "precision", "fp32")
    else:
        import os
        # the knob IS routed through GeoConfig.from_env; this is the
        # fallback for callers without a config (get_model factories)
        # graftlint: disable=GXL006 — config-less surface
        raw = os.environ.get("GEOMX_PRECISION", "fp32")
    alias = {"fp32": "fp32", "float32": "fp32", "f32": "fp32",
             "bf16": "bf16", "bfloat16": "bf16"}
    key = str(raw).lower()
    if key not in alias:
        raise ValueError(
            f"unknown precision {raw!r}: expected 'fp32' or 'bf16' "
            "(GEOMX_PRECISION / GeoConfig.precision)")
    return alias[key]


def make_loss_fn(apply_fn: Callable, mutable_keys=("batch_stats",),
                 compute_dtype=None, model_loss=None):
    """Loss closure over a flax apply_fn: ``loss_fn(params, model_state,
    x, y) -> (loss, (new_model_state, aux))``.  ``aux`` is a dict with
    ``accuracy`` and, optionally, ``counters`` (named scalars a step,
    which `fit` adds up in `LoopStats.counters`); the step's metrics are
    the loss and ``aux``.

    By default the loss is class-label cross-entropy on the model's whole
    logits.  ``model_loss``: the name of a method of a model that brings
    its own, ``method(x, y, train=True) -> (loss, aux)`` (a decoder's
    next-token loss, blocked so that no whole logits array lives).

    Images arrive uint8 NHWC; normalization to [0,1] happens on-device so
    the host->device transfer stays 1 byte/pixel.

    ``compute_dtype`` (e.g. ``jnp.bfloat16``) casts the normalized
    float inputs before the forward — the entry half of the bf16 mode;
    the models cast their own internals per-layer from the fp32 master
    params.  Integer token-id inputs pass through regardless.  The
    default (``None``) traces exactly the historical ops, keeping the
    disabled-path jaxpr byte-identical (tests/test_telemetry.py).
    """

    def loss_fn(params, model_state, x, y):
        x = _norm_input(x)
        if compute_dtype is not None and jnp.issubdtype(x.dtype,
                                                        jnp.floating):
            x = x.astype(compute_dtype)
        variables = {"params": params, **model_state}
        mut = [k for k in mutable_keys if k in model_state]
        if model_loss is not None:
            out = apply_fn(variables, x, y, train=True, method=model_loss,
                           **({"mutable": mut} if mut else {}))
            (loss, aux), new_model_state = out if mut else (out, model_state)
            return loss, (new_model_state, aux)
        if mut:
            logits, new_model_state = apply_fn(variables, x, train=True,
                                               mutable=mut)
        else:
            logits = apply_fn(variables, x, train=True)
            new_model_state = model_state
        aux = {"accuracy": jnp.mean(jnp.argmax(logits, -1) == y)}
        return cross_entropy_loss(logits, y), (new_model_state, aux)

    return loss_fn


def build_train_step(loss_fn: Callable, tx: optax.GradientTransformation,
                     sync: SyncAlgorithm, topology: HiPSTopology, mesh: Mesh,
                     donate: bool = True, config=None,
                     sp_model: bool = False):
    """Build `train_step(state, x, y) -> (state, metrics)`.

    - state leaves carry [num_parties, workers_per_party] replica axes;
    - x, y are [num_parties, workers_per_party, local_batch, ...];
    - metrics are global means (replicated scalars).

    With ``config.multi_gps`` set, leaves >= ``config.bigarray_bound``
    elements take the MultiGPS ZeRO-1 path (reduce_scatter -> shard-local
    optimizer -> all_gather over the worker axis; the dc-tier collective
    moves only the shard).  Requires FSA and a state initialized with
    shard-shaped optimizer/compressor leaves (Trainer handles this).

    ``sp_model``: the model runs in-graph collectives over the sp axis
    (Trainer sets this from the model's ``sp_mode``).  Sequence
    parallelism is a MODEL property, not just a mesh one: only an
    sp-aware model may receive sequence-sharded inputs and needs its
    shard-path grads SUMMED over sp.  A plain model on an sp mesh keeps
    replicated inputs and computes identical grads on every sp device —
    redundant but correct (no reduction needed), never silently sliced
    images.
    """
    sync.bind_topology(topology)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    sp = getattr(topology, "sp_degree", 1) if sp_model else 1
    # in-graph telemetry probes (telemetry/probes.py): the gate is
    # STATIC — resolved here, at build time — and guards the single
    # probe call site below, so the disabled path traces a jaxpr
    # byte-identical to a build with telemetry excised (pinned by
    # tests/test_telemetry.py)
    telem = _probes.telemetry_enabled(config)
    # Graft Pilot control operands (control/, docs/control.md): the same
    # static-gate contract — when GEOMX_CONTROL is on, sync_state
    # carries a "control" subtree of traced scalar operands (the bsc
    # ratio scale) that the dc-tier compressors read through a
    # trace-time context; when off, nothing here traces and the jaxpr is
    # byte-identical to a controller-excised build (pinned by
    # tests/test_control.py)
    from geomx_tpu.control.actuators import control_enabled
    ctl_on = control_enabled(config)

    mgps = None
    if config is not None and getattr(config, "multi_gps", False):
        from geomx_tpu.parallel.multigps import MultiGPSPlan
        from geomx_tpu.sync.fsa import FSA
        from geomx_tpu.sync.pipeline import PipelinedSync
        if sync.live_parties is not None:
            # fail loudly (same contract as the FSA check below): the
            # ZeRO-1 path calls the dc compressor directly and its big
            # leaves live as worker-axis shards — a masked renormalized
            # mean over sharded leaves needs per-shard re-layout this PR
            # does not implement
            raise ValueError(
                "GEOMX_MULTI_GPS does not compose with a degraded "
                "membership mask (resilience/): disable multi_gps or "
                "run with every party live")
        if isinstance(sync, PipelinedSync):
            # fail loudly (same contract as the FSA check below): the
            # ZeRO-1 update consumes the dc-tier shard in-step by
            # construction (reduce_scatter -> shard-local optimizer ->
            # all_gather), so there is no next-step slot to double-buffer
            # the collective into
            raise ValueError(
                "GEOMX_MULTI_GPS does not compose with "
                "GEOMX_PIPELINE_DEPTH: the sharded update needs this "
                "step's dc-tier result before the optimizer can run; "
                "disable one of the two")
        if not isinstance(sync, FSA):
            # fail loudly: a user "running MultiGPS" must not silently get
            # a replicated update (VERDICT r1 weak #2)
            raise ValueError(
                "GEOMX_MULTI_GPS requires sync_mode=fsa: the ZeRO-1 "
                "sharded update lives in gradient space; param-space "
                f"algorithms ({sync.name}) do not compose with it")
        mgps = MultiGPSPlan(config.bigarray_bound, topology.workers_per_party)
        from geomx_tpu.compression.base import NoCompressor
        from geomx_tpu.compression.bucketing import BucketedCompressor
        from geomx_tpu.sync.dgt import DGTCompressor
        if isinstance(sync.dc_compressor, BucketedCompressor):
            # MultiGPS keeps PER-LEAF dc semantics: big leaves cross the
            # WAN as 1/W worker-axis shards while small leaves stay
            # replicated, and the Trainer initializes shard-shaped
            # per-leaf compressor state (mixed_example).  Fusing shard
            # and replicated leaves into one bucket would pool their
            # top-k budgets across tensors that live on different
            # layouts, so unwrap back to the inner compressor here.
            sync.dc_compressor = sync.dc_compressor.inner
        if isinstance(sync.worker_compressor, DGTCompressor):
            # DGT's state is one flat schedule for the WHOLE gradient
            # (sync/dgt.py tree-level path); the MultiGPS update needs
            # per-leaf compressor state because big leaves bypass the
            # worker compressor entirely.  DGT is a WAN transport — put
            # it on the dc tier (where sync/__init__.py wires it); an
            # ICI-tier deferral would save nothing anyway.
            raise ValueError(
                "GEOMX_MULTI_GPS does not compose with DGT as the "
                "worker-tier compressor; configure DGT on the dc tier "
                "(enable_dgt wraps the dc compressor)")
        if not isinstance(sync.worker_compressor, NoCompressor):
            import warnings
            # big leaves' worker-tier reduce is the psum_scatter itself
            # (already a 1/W wire saving per link); a configured worker
            # compressor applies only to the small replicated leaves, and
            # the user should know the big ones bypass it (ADVICE r2 #1)
            warnings.warn(
                "multi_gps: leaves >= bigarray_bound use the sharded "
                "psum_scatter reduce and BYPASS the worker-tier "
                f"compressor ({sync.worker_compressor.name}); it still "
                "applies to smaller leaves", stacklevel=2)

    zplan = None
    if config is not None and getattr(config, "zero", False):
        from geomx_tpu.compression.base import NoCompressor
        from geomx_tpu.train.zero import ZeroPlan
        if mgps is not None:
            # fail loudly (same contract as the other composition
            # checks): both modes shard the weight update — MultiGPS
            # per-leaf, ZeRO per-bucket — and stacking them would shard
            # a shard
            raise ValueError(
                "GEOMX_ZERO does not compose with GEOMX_MULTI_GPS: both "
                "shard the weight update over the worker axis (ZeRO per "
                "fused bucket, MultiGPS per big leaf); pick one")
        zplan = getattr(sync, "zero_plan", None)
        if zplan is None:
            # rejects HFA (no shard form) and a non-bucketed dc engine,
            # and re-aligns the bucket padding so every bucket splits
            # into W lane-aligned shards (must happen before the first
            # trace).  bind_zero returns a bound COPY — the caller's
            # instance is never mutated; the Trainer binds up front and
            # passes the bound algorithm in, so its membership
            # recompiles land here with the plan already attached and
            # reuse it instead of re-binding per mask
            zplan = ZeroPlan(topology.workers_per_party)
            sync = sync.bind_zero(zplan)
        wc = getattr(sync, "worker_compressor",
                     getattr(getattr(sync, "inner", None),
                             "worker_compressor", None))
        if wc is not None and not isinstance(wc, NoCompressor):
            import warnings
            # the worker-tier reduce IS the psum_scatter (already a 1/W
            # wire saving per ICI link); a configured worker compressor
            # never runs — same contract as MultiGPS's big leaves
            warnings.warn(
                "GEOMX_ZERO: the worker-tier reduce is the bucket "
                "psum_scatter; the configured worker compressor "
                f"({wc.name}) is bypassed", stacklevel=2)

    # fused optimizer apply (ops/optim_pallas.py): the same static-gate
    # contract — resolved here at build time, and with the gate off the
    # update path below traces exactly the historical per-leaf optax
    # chain, keeping the default jaxpr byte-identical
    from geomx_tpu.ops.optim_pallas import (fused_apply, fused_optim_enabled,
                                            fused_spec_of)
    fopt_spec = None
    fopt_bucketer = None
    fopt_interp = False
    if fused_optim_enabled(config):
        fopt_spec = fused_spec_of(tx)
        if fopt_spec is None:
            # fail loudly (same contract as the composition checks
            # above): a plain optax closure hides its hyperparameters,
            # and silently falling back would report fused numbers from
            # an unfused run
            raise ValueError(
                "GEOMX_FUSED_OPTIM requires an optimizer built by "
                "ops.optim_pallas.fused_optimizer (the kernels need the "
                "static hyperparameters a plain optax closure hides)")
        if mgps is not None:
            raise ValueError(
                "GEOMX_FUSED_OPTIM does not compose with GEOMX_MULTI_GPS: "
                "the mixed shard/replicated per-leaf layout does not "
                "flatten into uniform buckets; use GEOMX_ZERO for a "
                "sharded fused update")
        if zplan is None:
            from geomx_tpu.compression.bucketing import BucketedCompressor
            from geomx_tpu.sync.pipeline import PipelinedCompressor
            dc = getattr(sync, "dc_compressor",
                         getattr(getattr(sync, "inner", None),
                                 "dc_compressor", None))
            if isinstance(dc, PipelinedCompressor):
                dc = dc.inner
            if not isinstance(dc, BucketedCompressor):
                raise ValueError(
                    "GEOMX_FUSED_OPTIM requires the bucketed dc-tier "
                    "engine (GEOMX_BUCKET_BYTES > 0): the kernels apply "
                    "the update over the flat fp32 buckets")
            fopt_bucketer = dc.zero_bucketer
        # interpret mode off-TPU (CI, CPU meshes): ops/dispatch.py's
        # answer, as for every other kernel.
        # GEOMX_FUSED_OPTIM_INTERPRET overrides it (=0 forces the native
        # Mosaic lowering, to cross-lower the step on a CPU host — such
        # a build LOWERS anywhere but only RUNS on TPU)
        import os as _os
        from geomx_tpu.ops.dispatch import kernel_mode
        # graftlint: disable=GXL006 — build-time gate
        _ov = _os.environ.get("GEOMX_FUSED_OPTIM_INTERPRET")
        if _ov is None:
            fopt_interp = kernel_mode() != "native"
        else:
            fopt_interp = _ov.strip().lower() not in ("0", "false", "")
        if zplan is not None:
            # the ZeRO shard-local update consumes the same kernels over
            # its 1/W bucket shards (train/zero.py reads these)
            zplan.fused_spec = fopt_spec
            zplan.fused_interpret = fopt_interp

    def _zero_sync_update(grads, params, opt_state, sync_state, step):
        """ZeRO (train/zero.py): reduce-scatter compressed buckets ->
        shard-local optimizer -> all_gather params.  The optimizer (and
        its state, allocated shard-shaped by Trainer.init_state) sees
        flat 1/W bucket shards; one all_gather per bucket rebuilds the
        replicated params for the next forward."""
        with profile_scope("step/sync_grads"):
            shard_g, sync_state = sync.sync_grad_shards(grads, params,
                                                        sync_state, step)
        with profile_scope("step/optimizer"):
            params, opt_state = zplan.apply_shard_update(
                tx, shard_g, params, opt_state, WORKER_AXIS)
        # param-space hook still runs on the rebuilt replicated params
        # (MixedSync's stale-pull refresh)
        with profile_scope("step/sync_params"):
            params, sync_state = sync.sync_params(params, sync_state, step)
        return params, opt_state, sync_state

    def _mgps_sync_update(grads, params, opt_state, sync_state, step):
        """MultiGPS: hierarchical reduce + optimizer with big leaves
        sharded 1/W across the worker axis (reference placement:
        src/kvstore/kvstore_dist.h:792-833)."""
        nw, np_ = topology.workers_per_party, topology.num_parties
        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_ws = treedef.flatten_up_to(sync_state["worker_comp"])
        widx = lax.axis_index(WORKER_AXIS)

        with profile_scope("step/sync_grads"):
            mixed_g, new_ws = [], []
            for p, g, ws in zip(flat_p, flat_g, flat_ws):
                if mgps.is_big(p.size):
                    # the scatter IS the worker-tier reduce (and compression:
                    # each link moves 1/W of the tensor)
                    mixed_g.append(mgps.scatter_grad_leaf(g, WORKER_AXIS))
                    new_ws.append(ws)
                else:
                    g, ws = sync.worker_compressor.allreduce_leaf(
                        g, ws, WORKER_AXIS, nw)
                    mixed_g.append(g / nw if nw > 1 else g)
                    new_ws.append(ws)
            # dc tier on the mixed tree: big leaves cross the WAN as shards
            dc = sync.dc_compressor
            if getattr(dc, "fuses_tree", False):
                # EXPLICIT composition with tree-fusing compressors (tree-
                # level DGT): one schedule per layout group.  A single flat
                # schedule over the whole mixed tree ranks blocks that mix
                # worker-axis shard content (different per worker slot) with
                # replicated leaves, so its send decisions differ across
                # workers and replicated leaves' aggregates diverge within a
                # party.  The split keeps the replicated group's schedule a
                # function of replicated content only (see
                # MultiGPSPlan.split_mixed; state initialized group-wise by
                # Trainer.init_state).
                sizes = [p.size for p in flat_p]
                big, small = mgps.split_mixed(sizes, mixed_g)
                dst = sync_state["dc_comp"]
                big_s, small_s = dst["sharded"], dst["replicated"]
                if big:
                    big, big_s = dc.allreduce(big, big_s, DC_AXIS, np_)
                if small:
                    small, small_s = dc.allreduce(small, small_s, DC_AXIS, np_)
                mixed_g = treedef.unflatten(
                    mgps.stitch_mixed(sizes, big, small))
                dstate = {"sharded": big_s, "replicated": small_s}
            else:
                mixed_g, dstate = dc.allreduce(
                    treedef.unflatten(mixed_g), sync_state["dc_comp"],
                    DC_AXIS, np_)
            if np_ > 1:
                mixed_g = jax.tree.map(lambda x: x / np_, mixed_g)

        with profile_scope("step/optimizer"):
            mixed_p = treedef.unflatten([
                mgps.shard_param_leaf(p, widx) if mgps.is_big(p.size) else p
                for p in flat_p])
            updates, opt_state = tx.update(mixed_g, opt_state, mixed_p)
            new_mixed = optax.apply_updates(mixed_p, updates)
            params = treedef.unflatten([
                mgps.unshard_param_leaf(nm, p, WORKER_AXIS)
                if mgps.is_big(p.size) else nm
                for p, nm in zip(flat_p, treedef.flatten_up_to(new_mixed))])
        sync_state = {"dc_comp": dstate,
                      "worker_comp": treedef.unflatten(new_ws)}
        return params, opt_state, sync_state

    def _device_step(state: TrainState, x, y):
        def squeeze(t):
            return jax.tree.map(lambda a: a[0, 0], t)

        def expand(t):
            return jax.tree.map(lambda a: a[None, None], t)
        params = squeeze(state.params)
        opt_state = squeeze(state.opt_state)
        model_state = squeeze(state.model_state)
        sync_state = squeeze(state.sync_state)
        step = state.step
        xb, yb = x[0, 0], y[0, 0]

        ctl = None
        if ctl_on:
            # detach the control operands before the sync hooks (whose
            # state-threading rebuilds dicts and would drop foreign
            # keys) and open them as a trace-time context for the
            # compressors; they rejoin the output sync_state below so
            # host-side actuation rewrites them without a recompile
            from geomx_tpu.control.actuators import CONTROL_KEY
            sync_state = dict(sync_state)
            ctl = sync_state.pop(CONTROL_KEY, None)
            if ctl is None:
                raise ValueError(
                    "GEOMX_CONTROL is on but sync_state carries no "
                    "control operands: initialize the state with a "
                    "control-enabled Trainer (init_state adds the "
                    f"{CONTROL_KEY!r} subtree)")

        # the scopes below are the step's layer boundaries, one fixed
        # vocabulary (telemetry/layers.py): metadata on the ops traced
        # under them, never an op of their own
        with profile_scope("step/forward_backward"):
            fwd_params = sync.forward_params(params, sync_state)
            (loss, (model_state, aux)), grads = grad_fn(
                fwd_params, model_state, xb, yb)

            if sp > 1:
                # sequence parallelism: each sp device back-propagated
                # only its sequence shard's path (the model's forward
                # psum/attention collectives ride the sp axis); the true
                # gradient is the SUM of the shard contributions.  After
                # this, grads are identical across sp and the dc/worker
                # sync tiers see one consistent replica per (party,
                # worker).
                grads = lax.psum(grads, SP_AXIS)
                model_state = jax.tree.map(
                    lambda a: lax.pmean(a, SP_AXIS)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a,
                    model_state)

        # kept for the probes: this device's gradients before any
        # cross-party aggregation (pure aliases — no traced ops)
        raw_grads = grads
        synced_grads = None
        probe_ctx = _probes.inline_collection() if telem \
            else contextlib.nullcontext(None)
        if ctl is not None:
            from geomx_tpu.control.actuators import control_operands
            ctl_ctx = control_operands(ctl)
        else:
            ctl_ctx = contextlib.nullcontext(None)
        with probe_ctx as inline_sink, ctl_ctx:
            if mgps is not None:
                params, opt_state, sync_state = _mgps_sync_update(
                    grads, params, opt_state, sync_state, step)
            elif zplan is not None:
                # ZeRO: sync+update fuse like MultiGPS, and the synced
                # gradient exists only as this worker's shard — the
                # replicated-value probes are skipped rather than
                # misreporting one shard under a replicated out-spec
                params, opt_state, sync_state = _zero_sync_update(
                    grads, params, opt_state, sync_state, step)
            else:
                with profile_scope("step/sync_grads"):
                    grads, sync_state = sync.sync_grads(grads, params,
                                                        sync_state, step)
                # only algorithms whose sync output is mesh-replicated
                # feed the replicated-value probes (HFA's identity
                # sync_grads keeps per-device gradients, and publishing
                # one shard's local value under a replicated out-spec
                # would silently misreport)
                if sync.grads_replicated_after_sync:
                    synced_grads = grads
                with profile_scope("step/optimizer"):
                    if fopt_spec is not None:
                        # fused apply: params and grads flatten onto the
                        # bucket layout the dc tier already defined
                        # (opt_state lives on the same layout —
                        # Trainer.init_state), one Pallas pass per bucket
                        flat_p, tdef = jax.tree.flatten(params)
                        bk = fopt_bucketer(flat_p)
                        new_pb, opt_state = fused_apply(
                            fopt_spec, bk.flatten(flat_p),
                            bk.flatten(tdef.flatten_up_to(grads)),
                            opt_state, interpret=fopt_interp)
                        params = tdef.unflatten(bk.unflatten(new_pb))
                    else:
                        updates, opt_state = tx.update(grads, opt_state,
                                                       params)
                        params = optax.apply_updates(params, updates)
                with profile_scope("step/sync_params"):
                    params, sync_state = sync.sync_params(
                        params, sync_state, step)
            with profile_scope("step/sync_model_state"):
                model_state, sync_state = sync.sync_model_state(
                    model_state, sync_state, step)
        if ctl is not None:
            # operands pass through unchanged (actuation is host-side);
            # rejoining after the hooks keeps the state structure stable
            # whatever dicts the algorithm rebuilt
            from geomx_tpu.control.actuators import CONTROL_KEY
            sync_state = dict(sync_state, **{CONTROL_KEY: ctl})

        with profile_scope("step/metrics"):
            metrics = {"loss": loss, **aux}
            # global mean over every worker for reporting
            if sp > 1:
                metrics = jax.lax.pmean(metrics, SP_AXIS)
            metrics = jax.lax.pmean(metrics, WORKER_AXIS)
            pw = sync.party_weight()
            if pw is None:
                metrics = jax.lax.pmean(metrics, DC_AXIS)
            else:
                # degraded membership: report the mean over SURVIVORS — a
                # dead party's loss/accuracy describes data that never
                # reached the aggregate
                metrics = jax.tree.map(
                    lambda x: jax.lax.psum(x * pw, DC_AXIS) / sync.num_live,
                    metrics)
            # step metadata: the live-party count baked into this traced
            # step (static — the membership epoch is a recompile boundary);
            # tests/test_resilience.py reads it back as evidence that
            # degraded steps really ran the renormalized survivor mean
            metrics["num_live_parties"] = jnp.asarray(sync.num_live,
                                                      jnp.float32)
            if telem:
                # step-health probes ride the replicated metrics output
                # (every value is mesh-replicated by construction); the host
                # plane (Trainer fit loop) publishes them to the metric
                # registry and the event log
                metrics["telemetry"] = _probes.collect_step_probes(
                    raw_grads, synced_grads, sync, sync_state, inline_sink,
                    params)
                if ctl is not None:
                    # the live ratio scale rides the probe dict so the
                    # registry (and the controller's own sensors) see the
                    # operand the step actually ran with — replicated by
                    # construction (every device holds the same state copy)
                    metrics["telemetry"]["control_ratio_scale"] = \
                        ctl["bsc_ratio_scale"]

        # where the compiler fuses an optimizer update on its own (a
        # decoder's gradients leave a loop), the fusion's root is the
        # reshape that puts the mesh dims back on the leaf, and the update
        # takes that reshape's scope: the optimizer's
        with profile_scope("step/optimizer"):
            new_params, new_opt_state = expand(params), expand(opt_state)
        new_state = TrainState(
            step=step + 1,
            params=new_params,
            opt_state=new_opt_state,
            model_state=expand(model_state),
            sync_state=expand(sync_state),
        )
        return new_state, metrics

    specs = state_specs()
    batch_spec = P(DC_AXIS, WORKER_AXIS)
    x_spec = batch_spec
    if sp > 1:
        # token batches [P, W, B, L(, ...)]: the sequence dim shards
        # over sp; state and labels replicate across sp (grads are
        # psum'd back to consistency inside the step)
        x_spec = P(DC_AXIS, WORKER_AXIS, None, SP_AXIS)
    mapped = shard_map_compat(
        _device_step, mesh,
        in_specs=(specs, x_spec, batch_spec),
        out_specs=(specs, P()),
    )
    if donate:
        return jax.jit(mapped, donate_argnums=(0,))
    return jax.jit(mapped)


def build_eval_step(apply_fn: Callable):
    """Single-program eval on unreplicated params (any one device).
    Returns (eval_step, logits_fn); both share the one normalization
    convention (uint8 -> [0,1] on device)."""

    @jax.jit
    def logits_fn(params, model_state, x):
        x = _norm_input(x)
        variables = {"params": params, **model_state}
        return apply_fn(variables, x, train=False)

    @jax.jit
    def eval_step(params, model_state, x, y):
        logits = logits_fn(params, model_state, x)
        pred = jnp.argmax(logits, -1)
        return jnp.sum(pred == y), jnp.asarray(y.shape[0], jnp.int32)

    return eval_step, logits_fn
