"""High-level Trainer: the reference's examples/cnn*.py loop as a library.

Wires model + optimizer + sync algorithm + topology into a fit loop with
per-iteration metrics, mirroring the reference workload's observable output
("[Time t][Epoch e][Iteration i] Test Acc a", examples/cnn.py:129-131) and
its JSON measurement reporter (examples/utils.py:120-192).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from geomx_tpu.config import GeoConfig
from geomx_tpu.data.loader import GeoDataLoader
from geomx_tpu.sync import get_sync_algorithm
from geomx_tpu.sync.base import SyncAlgorithm
from geomx_tpu.telemetry import layers
from geomx_tpu.topology import HiPSTopology
from geomx_tpu.train.state import (TrainState, replicate_consuming,
                                   replicate_tree, unreplicate_tree)
from geomx_tpu.train.step import build_eval_step, build_train_step, make_loss_fn
from geomx_tpu.utils.metrics import Measure


class Trainer:
    def __init__(self, model, topology: HiPSTopology,
                 optimizer: optax.GradientTransformation,
                 sync: Optional[SyncAlgorithm] = None,
                 config: Optional[GeoConfig] = None,
                 mesh=None, donate: bool = True,
                 single_device_model=None):
        """``single_device_model``: a twin of ``model`` with the same
        parameter structure but no in-graph collectives, used for the
        un-meshed paths (init, eval, predict).  Required when ``model``
        calls axis collectives (e.g. sequence-parallel attention over the
        sp axis), which only trace inside the sharded train step."""
        # the lifecycle record (telemetry/layers.py): set-up's spans, the
        # allocator at the edges of set-up and of each fit, and the
        # process's CompileLog, which the first trainer installs
        self.lifecycle = layers.Lifecycle(layers.compile_log())
        with self.lifecycle.span("setup/build"):
            self._build(model, topology, optimizer, sync, config, mesh,
                        donate, single_device_model)
        self.lifecycle.step_fun = getattr(self.train_step, "__name__", None)
        # the devices, not the trainer: the record outlives it
        # (layers.last_lifecycle)
        self.lifecycle.memory_source = functools.partial(
            layers.fullest_device_stats, list(self.mesh.devices.flat))

    def _build(self, model, topology, optimizer, sync, config, mesh, donate,
               single_device_model):
        self.model = model
        self._sd_model = single_device_model or model
        self.topology = topology
        self.config = config or GeoConfig(
            num_parties=topology.num_parties,
            workers_per_party=topology.workers_per_party)
        self.sync = sync if sync is not None else get_sync_algorithm(self.config)
        self.mesh = mesh if mesh is not None else topology.build_mesh()
        self.tx = optimizer
        # compute precision (train/step.resolve_precision): under bf16
        # the loss closure casts the normalized float inputs and the
        # models cast their own internals per-op from the fp32 master
        # params — nothing that accumulates ever leaves fp32, so there
        # is no loss scaling to configure (docs/performance.md)
        from geomx_tpu.train.step import resolve_precision
        self._precision = resolve_precision(self.config)
        compute_dtype = jnp.bfloat16 if self._precision == "bf16" else None
        # a model that brings its own loss (models/kimi_linear.py) has a
        # method `loss_and_aux(x, y, train)`
        self.loss_fn = make_loss_fn(
            model.apply, compute_dtype=compute_dtype,
            model_loss="loss_and_aux" if hasattr(model, "loss_and_aux")
            else None)
        if self._precision == "bf16":
            mdt = getattr(model, "dtype", None)
            if mdt is None or mdt == jnp.float32:
                import warnings
                # the input cast alone buys nothing if the model's
                # layers immediately promote back to fp32
                warnings.warn(
                    "GEOMX_PRECISION=bf16 but the model's compute dtype "
                    f"is {mdt!r}: its layers will promote back to fp32. "
                    "Build the model with a bf16 dtype (e.g. "
                    "get_model(name, precision='bf16')) to realize the "
                    "mixed-precision speedup", stacklevel=2)
        # fused optimizer apply (ops/optim_pallas.py): resolved here so
        # init_state allocates optimizer state on the bucket layout the
        # fused path updates; build_train_step re-checks the gate and
        # validates the optimizer/compressor stack
        from geomx_tpu.ops.optim_pallas import fused_optim_enabled
        self._fused_optim = fused_optim_enabled(self.config)
        # input-pipeline overlap depth (data/loader.py): how many
        # assembled+device_put batches the producer thread keeps in
        # flight ahead of the step; 0 = synchronous
        self._prefetch = max(0, int(getattr(self.config, "prefetch", 2)))
        sp_model = getattr(model, "sp_mode", None) is not None
        if getattr(topology, "sp_degree", 1) > 1 and not sp_model:
            import warnings
            warnings.warn(
                f"topology has sp_degree={topology.sp_degree} but the "
                "model declares no sp_mode: inputs stay replicated over "
                "the sp axis and every sp device computes the same thing "
                "— correct but wasted chips. Use an sp-aware model (e.g. "
                "SeqClassifier(sp_mode='ring')) or sp_degree=1.",
                RuntimeWarning, stacklevel=2)
        self._sp_model = sp_model
        self._donate = donate
        # ZeRO-sharded weight update (train/zero.py, GEOMX_ZERO): bind
        # the plan HERE, onto the bound copy bind_zero returns, so the
        # trainer's own sync carries it (shard-shaped state init, the
        # sharded drain program, checkpoint/catch-up layout) and
        # build_train_step — including every membership recompile —
        # reuses one plan.  The caller's sync instance is never mutated.
        if getattr(self.config, "zero", False):
            if getattr(self.sync, "supports_zero", False) \
                    and self.sync.zero_plan is None:
                from geomx_tpu.train.zero import ZeroPlan
                self.sync = self.sync.bind_zero(
                    ZeroPlan(topology.workers_per_party))
        elif getattr(self.sync, "zero_plan", None) is not None:
            raise ValueError(
                "sync algorithm is ZeRO-bound (zero_plan set) but this "
                "trainer's config has zero=False: the step program would "
                "run the replicated update against shard-shaped sync "
                "state.  Pass a fresh (unbound) sync algorithm, or "
                "enable GEOMX_ZERO/GeoConfig(zero=True) to match")
        self.train_step = build_train_step(
            self.loss_fn, self.tx, self.sync, topology, self.mesh,
            donate=donate, config=self.config, sp_model=sp_model)
        # membership epochs (resilience/): the live-party mask currently
        # bound into self.train_step; None = every party live.  Each
        # distinct mask owns one compiled step program (the recompile
        # boundary), cached so a blackout/re-admit cycle compiles twice,
        # not per transition.
        self._membership: Optional[tuple] = None
        self._membership_version = 0
        self._step_cache = {None: self.train_step}
        self._mgps = None
        if self.config.multi_gps:
            from geomx_tpu.parallel.multigps import MultiGPSPlan
            self._mgps = MultiGPSPlan(self.config.bigarray_bound,
                                      topology.workers_per_party)
        # ZeRO-sharded weight update (train/zero.py, GEOMX_ZERO):
        # build_train_step bound the plan into the sync algorithm; the
        # Trainer needs it for shard-shaped state init, the sharded
        # drain program, and checkpoint/catch-up layout handling
        self._zero_plan = getattr(self.sync, "zero_plan", None)
        self.eval_step, self._logits_fn = build_eval_step(
            self._sd_model.apply)
        self._batch_sharding = topology.batch_sharding(self.mesh)
        self._drain_step = None       # lazily-built pipeline drain program
        self._epoch_runners: dict = {}
        self._eval_cache: dict = {}    # device-resident test set
        self._eval_sweeps: dict = {}   # batch_size -> scanned eval program
        # telemetry plane (docs/telemetry.md): when enabled, the fit
        # loop publishes the in-graph step probes to the metric registry
        # and the event log at the same boundaries it already syncs for
        # logging (no extra device round trips)
        from geomx_tpu.telemetry.probes import telemetry_enabled
        self._telemetry = telemetry_enabled(self.config)
        # Graft Pilot (control/, docs/control.md): when enabled, the
        # sync_state carries traced control operands (init_state adds
        # them) and apply_control is the actuation boundary — ratio
        # rewrites are operand swaps (no recompile), depth switches are
        # cached recompiles modeled on apply_membership
        from geomx_tpu.control.actuators import control_enabled
        self._control = control_enabled(self.config)
        self._control_cache: dict = {}   # (depth, membership) -> step_fn
        # graft auditor (analysis/, docs/analysis.md): when enabled, the
        # fit loop captures the active step program's collective
        # signature once (cheap: one abstract trace) and every
        # apply_membership recompile is diffed against it — a membership
        # mask must change CONSTANTS, never the collective sequence, or
        # live and recovering parties deadlock/diverge at the next epoch
        from geomx_tpu.analysis import audit_enabled, audit_severity_gate
        self._audit = audit_enabled(self.config)
        self._audit_gate = audit_severity_gate(self.config) \
            if self._audit else "error"
        self._audit_args = None     # (state, x, y) ShapeDtypeStructs
        # the step's abstract arguments (shapes, dtypes, shardings), taken
        # from the first batch a fit sees, and the counters of the last
        # fit's host loop (telemetry/layers.py)
        self._step_args = None
        self.loop_stats: Optional[layers.LoopStats] = None
        self._audit_sigs: dict = {}  # membership key -> signature
        self._telem_last_it = 0
        # flight recorder (telemetry/flight.py, GEOMX_FLIGHT): a bounded
        # ring of per-step records with deterministic anomaly rules and
        # forensics auto-dumps, fed at the same publish boundaries as
        # the registry.  Rides the probes — without telemetry there is
        # nothing to record, so that misconfig warns instead of
        # silently recording empty rings.
        from geomx_tpu.telemetry.flight import (flight_recorder_from_config,
                                                install_incident_recorder)
        self._flight = flight_recorder_from_config(self.config)
        if self._flight is not None:
            # host-plane incidents (server/scheduler restarts, wire-CRC
            # rejections — notify_host_incident) land in the bounded
            # incident ring, so forensics bundles show recovery
            # activity next to the step records
            install_incident_recorder(self._flight)
        self._attr_window_us = None  # trace mark of the last flight window
        if self._flight is not None and not self._telemetry:
            import warnings
            warnings.warn(
                "GEOMX_FLIGHT is on but telemetry is off: the flight "
                "recorder rides the in-graph step probes — enable "
                "GEOMX_TELEMETRY/GeoConfig(telemetry=True) or the ring "
                "records nothing", RuntimeWarning, stacklevel=2)
        # run capsule (telemetry/capsule.py, GEOMX_CAPSULE): whole-run
        # observability capture — per-step sensor records at the same
        # publish boundary as the flight ring, the link journal via the
        # observatory tap, periodic registry samples, and the archive
        # written at every fit end (atomic; tools/runcap.py reads it)
        from geomx_tpu.telemetry.capsule import capsule_from_config
        self._capsule = capsule_from_config(self.config)
        if self._capsule is not None:
            from geomx_tpu.telemetry.links import get_link_observatory
            self._capsule.attach_observatory(get_link_observatory())
            self._capsule.sampler.start()
            # the sampler thread and the observatory tap must not
            # outlive the trainer: a process constructing many
            # capsule-armed trainers (repeated experiments, notebooks)
            # would otherwise leak one registry-walking daemon each.
            # The finalizer holds the capsule, never the trainer —
            # close_capsule() is the deterministic path.
            import weakref
            weakref.finalize(self, self._capsule.sampler.stop)
            weakref.finalize(self, self._capsule.detach_observatory)
            if not self._telemetry:
                import warnings
                warnings.warn(
                    "GEOMX_CAPSULE is on but telemetry is off: the "
                    "capsule's step records ride the published probes "
                    "— enable GEOMX_TELEMETRY/GeoConfig(telemetry="
                    "True) or the archive captures no sensor stream",
                    RuntimeWarning, stacklevel=2)
        self._event_log = None
        events_path = getattr(self.config, "telemetry_events", "")
        if events_path:
            from geomx_tpu.telemetry.export import (EventLog,
                                                    set_default_event_log)
            self._event_log = EventLog(events_path)
            # make this the process default too, so subsystems that only
            # know the global log_event() (membership transitions, relay
            # failures) land in the SAME file as the step probes
            set_default_event_log(self._event_log)

    def init_state(self, rng: jax.Array, sample_input: np.ndarray) -> TrainState:
        """sample_input: one local batch [b, H, W, C] (uint8 images) or
        [b, L] (integer token ids — passed through un-normalized)."""
        from geomx_tpu.train.step import _norm_input
        life = self.lifecycle
        life.mark("setup/init_state:begin")
        with life.span("setup/init_state"):
            with life.span("setup/model_init"):
                x0 = _norm_input(jnp.asarray(sample_input))
                # jit the init: one compiled program instead of thousands of
                # eager dispatches
                variables = jax.jit(
                    lambda r, x: self._sd_model.init(r, x, train=False))(rng, x0)
            variables = dict(variables)
            params = variables.pop("params")
            model_state = variables  # batch_stats etc.
            with life.span("setup/state_init"):
                if self._mgps is not None:
                    # MultiGPS ZeRO-1: optimizer + compressor state for big
                    # leaves is allocated per worker-axis shard (the 1/W memory
                    # saving); every (dc, worker) slot tracks only its own shard
                    mixed = self._mgps.mixed_example(params)
                    opt_state = self.tx.init(mixed)
                    sync_state = self.sync.init_state(mixed,
                                                      model_state=model_state)
                    dc = getattr(self.sync, "dc_compressor", None)
                    if dc is not None and getattr(dc, "fuses_tree", False):
                        # tree-fusing dc compressors (tree-level DGT) run one
                        # flat schedule per layout group under MultiGPS — shard
                        # leaves and replicated leaves must not share blocks
                        # (train/step.py _mgps_sync_update splits the same way)
                        sizes = [leaf.size for leaf in jax.tree.leaves(params)]
                        big, small = self._mgps.split_mixed(
                            sizes, jax.tree.leaves(mixed))
                        sync_state = dict(sync_state, dc_comp={
                            "sharded": dc.init_state(big),
                            "replicated": dc.init_state(small)})
                elif self._zero_plan is not None:
                    # ZeRO: the optimizer runs on flat 1/W bucket shards, so its
                    # state is allocated shard-shaped — the per-chip memory
                    # saving IS this allocation.  The sync algorithm's zero-
                    # aware init sizes the dc-tier EF residuals the same way.
                    shards = self._zero_plan.shard_example(
                        params, self._zero_plan.bucketed)
                    opt_state = self.tx.init(shards)
                    sync_state = self.sync.init_state(params,
                                                      model_state=model_state)
                elif self._fused_optim:
                    # fused apply: the optimizer state lives on the flat bucket
                    # layout (one fp32 vector per bucket, lane-padded sizes) —
                    # the same layout the dc tier already fuses gradients onto,
                    # so the kernels update params, moments and wire buckets in
                    # one coordinate system
                    from geomx_tpu.compression.bucketing import BucketedCompressor
                    from geomx_tpu.sync.pipeline import PipelinedCompressor
                    dc = getattr(self.sync, "dc_compressor",
                                 getattr(getattr(self.sync, "inner", None),
                                         "dc_compressor", None))
                    if isinstance(dc, PipelinedCompressor):
                        dc = dc.inner
                    if not isinstance(dc, BucketedCompressor):
                        raise ValueError(
                            "GEOMX_FUSED_OPTIM requires the bucketed dc-tier "
                            "engine (GEOMX_BUCKET_BYTES > 0): the kernels apply "
                            "the update over the flat fp32 buckets")
                    bk = dc.zero_bucketer(jax.tree.leaves(params))
                    opt_state = self.tx.init(
                        [jnp.zeros((n,), jnp.float32) for n in bk.bucket_sizes])
                    sync_state = self.sync.init_state(params,
                                                      model_state=model_state)
                else:
                    opt_state = self.tx.init(params)
                    sync_state = self.sync.init_state(params,
                                                      model_state=model_state)
                if self._control:
                    # control operands join sync_state so they ride the traced
                    # step as INPUTS: retuning them is a host-side rewrite of
                    # one scalar leaf, never a recompile (control/actuators.py)
                    from geomx_tpu.control.actuators import (CONTROL_KEY,
                                                             init_control_operands)
                    if not isinstance(sync_state, dict):
                        raise ValueError(
                            "GEOMX_CONTROL needs a dict-shaped sync state to "
                            f"carry its operands; {self.sync.name!r} returns "
                            f"{type(sync_state).__name__}")
                    sync_state = dict(sync_state)
                    sync_state[CONTROL_KEY] = init_control_operands()
            # the replicated scalar must carry the SAME NamedSharding the
            # compiled step emits for it: a SingleDeviceSharding here makes
            # the second train_step/epoch-runner call a jit cache MISS (the
            # input sharding is part of the key) — one full recompile
            from jax.sharding import NamedSharding, PartitionSpec
            step = jax.device_put(jnp.zeros((), jnp.int32),
                                  NamedSharding(self.mesh, PartitionSpec()))
            # leaf by leaf, each source let go once its copy exists: the
            # device never holds the state twice
            parts = [params, opt_state, model_state, sync_state]
            del params, opt_state, model_state, sync_state, variables
            with life.span("setup/replicate"):
                params, opt_state, model_state, sync_state = replicate_consuming(
                    parts, self.topology, self.mesh)
            state = TrainState(step=step, params=params,
                               opt_state=opt_state, model_state=model_state,
                               sync_state=sync_state)
        life.mark("setup/init_state:end")
        return state

    def make_loader(self, x, y, batch_size: int, split_by_class: bool = False,
                    seed: int = 0, augment: bool = False,
                    device_cache: bool = False,
                    seq_sharded: Optional[bool] = None) -> GeoDataLoader:
        """``seq_sharded``: shard x's sequence dim over the sp axis
        (requires an sp topology).  Default: auto — wide-integer
        [N, L(, feat)] token batches on an sp topology; uint8 data
        (images) and floats keep plain replica sharding."""
        dtype = getattr(x, "dtype", None)
        ndim = getattr(x, "ndim", 0)
        if seq_sharded is None:
            seq_sharded = (
                getattr(self.topology, "sp_degree", 1) > 1
                and getattr(self.model, "sp_mode", None) is not None
                and dtype is not None
                and np.issubdtype(dtype, np.integer)
                and dtype != np.uint8 and ndim in (2, 3))
        sharding = self._batch_sharding
        if seq_sharded:
            sharding = (self.topology.seq_batch_sharding(self.mesh),
                        self._batch_sharding)
        return GeoDataLoader(x, y, self.topology, batch_size,
                             split_by_class=split_by_class, seed=seed,
                             sharding=sharding, augment=augment,
                             device_cache=device_cache)

    # ---- membership epochs (resilience/) ----------------------------------

    def apply_membership(self, state: TrainState, epoch,
                         policy: Optional[str] = None) -> TrainState:
        """Bind a new membership epoch (a ``MembershipEpoch`` or a
        live-party mask) — the recompile boundary of the resilience
        subsystem.

        Rebinds the sync algorithm to the mask, swaps ``train_step`` to
        the mask's compiled program (built on first use, cached after),
        and applies the residual policy to ``state.sync_state``:
        ``"reset"`` (default; ``GEOMX_RESILIENCE_RESIDUALS``) discards
        dc-tier error-feedback residuals and pipeline in-flight buffers
        accumulated under the old membership, ``"carry"`` keeps them
        (docs/resilience.md).  Returns the adjusted state; a no-op when
        the mask is unchanged.

        Re-admission: call :meth:`catchup_payload` for the state blob
        the returning party installs (``admit_party``) BEFORE this
        rebind widens the collective back over it."""
        from geomx_tpu.topology import normalize_live_mask
        mask = normalize_live_mask(getattr(epoch, "live_mask", epoch),
                                   self.topology.num_parties)
        key = None if all(mask) else mask
        if key == self._membership:
            return state
        if self._mgps is not None:
            raise ValueError(
                "GEOMX_MULTI_GPS does not compose with membership "
                "changes (resilience/): the ZeRO-1 shards have no "
                "renormalized-survivor form")
        if policy is None:
            # config-first, like every other knob: GeoConfig.from_env is
            # where GEOMX_RESILIENCE_RESIDUALS folds in, so an explicit
            # GeoConfig(resilience_residuals=...) must not be overridden
            # by a stale env var
            policy = getattr(self.config, "resilience_residuals",
                             None) or "reset"
        if policy not in ("reset", "carry"):
            # validate BEFORE any rebinding: a bad policy must not leave
            # the trainer half-switched to the new mask
            raise ValueError(f"unknown residual policy {policy!r}: "
                             "expected 'reset' or 'carry'")
        self.sync.bind_membership(mask)
        self._membership = key
        self._membership_version = getattr(epoch, "version",
                                           self._membership_version + 1)
        step_fn = self._step_cache.get(key)
        if step_fn is None:
            step_fn = build_train_step(
                self.loss_fn, self.tx, self.sync, self.topology,
                self.mesh, donate=self._donate, config=self.config,
                sp_model=self._sp_model)
            self._step_cache[key] = step_fn
        # graft auditor at the recompile boundary (GEOMX_AUDIT): the
        # new membership's program must trace the SAME ordered
        # collective sequence as the reference program — masking changes
        # constants, never collectives.  Raises AuditError (before the
        # swap) on divergence at/above the severity gate; call
        # apply_membership again after fixing the config to rebind.
        self._audit_membership_program(key, step_fn)
        self.train_step = step_fn
        # both close over the previous membership's traced program
        self._epoch_runners.clear()
        self._drain_step = None
        if self._zero_plan is not None and policy == "carry":
            # ZeRO + carry: the dc-tier state holds per-WORKER shard
            # content, which the (0, 0)-copy round trip below would
            # silently broadcast over every worker slot.  Carry is an
            # identity on sync state for every membership-capable
            # algorithm, so keep the device arrays untouched.
            return state
        # residual/buffer policy, applied host-side on copy (0, 0) and
        # re-replicated (sync state is identical across replicas for
        # every membership-capable algorithm; under ZeRO the reset
        # branch replaces the only worker-distinct subtree — dc_comp —
        # with freshly-initialized shard-shaped zeros, which broadcast
        # correctly)
        new_ss = self.sync.reset_comm_state(
            unreplicate_tree(state.params),
            unreplicate_tree(state.sync_state), policy)
        return TrainState(
            step=state.step, params=state.params,
            opt_state=state.opt_state, model_state=state.model_state,
            sync_state=replicate_tree(new_ss, self.topology, self.mesh))

    # ---- graft auditor (analysis/, docs/analysis.md) ----------------------

    def _step_signature(self, step_fn):
        """Collective signature + single-program consistency findings of
        a step program, traced on the abstract (ShapeDtypeStruct)
        reference arguments captured by the fit loop."""
        from geomx_tpu.analysis import (AuditContext,
                                        CollectiveConsistencyPass)
        st, xb, yb = self._audit_args
        ctx = AuditContext()
        findings = CollectiveConsistencyPass().run(
            jax.make_jaxpr(step_fn)(st, xb, yb), ctx)
        return ctx.extras["collective_signature"], findings

    def _abstract_step_args(self, state: TrainState, xb, yb):
        """(state, x, y) as ``jax.ShapeDtypeStruct`` trees with their
        shardings: what the active step program can be traced or lowered
        from again.  Taken once per program, from the first batch seen."""
        if self._step_args is None:
            self._step_args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=getattr(a, "sharding", None)),
                (state, xb, yb))
        return self._step_args

    def _audit_capture(self, state: TrainState, xb, yb) -> None:
        """Arm the auditor: record abstract step arguments and the
        active program's collective signature (once per Trainer; the
        first fit batch with GEOMX_AUDIT on).  One abstract trace — no
        compile, no device work."""
        if not self._audit or self._audit_args is not None:
            return
        self._audit_args = self._abstract_step_args(state, xb, yb)
        self._audit_sigs[self._membership] = self._step_signature(
            self.train_step)

    def _audit_membership_program(self, key, step_fn) -> None:
        """Audit the membership program about to be installed: its
        collective signature is diffed against the armed reference
        (divergence is GX-COLLECTIVE-002, always error severity — a
        program pair that deadlocks has no soft form) and the program's
        own consistency findings (e.g. the axis_index_groups warning)
        join in.  Findings at/above GEOMX_AUDIT_SEVERITY raise
        AuditError; below it they surface as warnings."""
        if not self._audit or self._audit_args is None:
            return
        cached = self._audit_sigs.get(key)
        if cached is None:
            cached = self._step_signature(step_fn)
            self._audit_sigs[key] = cached
        sig, prog_findings = cached
        ref_key, (ref, _) = next(iter(self._audit_sigs.items()))
        if key == ref_key:
            return
        from geomx_tpu.analysis import (diff_collective_signatures,
                                        enforce)
        findings = enforce(list(prog_findings) + diff_collective_signatures(
            {f"membership={ref_key}": ref, f"membership={key}": sig},
            rule_id="GX-COLLECTIVE-002"), self._audit_gate)
        if findings:  # below the gate: surface without stopping the run
            import warnings
            warnings.warn("\n".join(f.format() for f in findings),
                          RuntimeWarning, stacklevel=3)

    # ---- Graft Pilot actuation boundary (control/, docs/control.md) -------

    def _dc_ratio_compressor(self):
        """The ratio-bearing dc-tier compressor (BiSparse, possibly
        under MPQ), unwrapped through the Pipelined/Bucketed layers;
        None when the dc tier carries no top-k ratio."""
        dc = getattr(self.sync, "dc_compressor", None)
        if dc is None:
            dc = getattr(getattr(self.sync, "inner", None),
                         "dc_compressor", None)
        while dc is not None and not hasattr(dc, "ratio") \
                and hasattr(dc, "inner"):
            dc = dc.inner
        if dc is not None and not hasattr(dc, "ratio"):
            # MPQ routes large tensors to its BiSparse half
            dc = getattr(dc, "large", None)
        return dc if dc is not None and hasattr(dc, "ratio") else None

    def control_depth(self) -> int:
        """The pipeline depth currently compiled in (0 or 1)."""
        from geomx_tpu.sync.pipeline import PipelinedSync
        return 1 if isinstance(self.sync, PipelinedSync) else 0

    def apply_control(self, state: TrainState, decision) -> TrainState:
        """Apply one Graft Pilot decision — the control subsystem's
        actuation boundary (docs/control.md).

        - ``kind == "ratio"``: rewrite the ``bsc_ratio_scale`` operand
          in ``sync_state["control"]`` host-side with the SAME sharding
          the compiled step expects — the jit cache stays warm, no
          recompile (tests/test_control.py pins the cached-executable
          count).
        - ``kind == "depth"``: wrap/unwrap ``PipelinedSync`` — a
          recompile boundary modeled on :meth:`apply_membership`
          (per-decision cached step programs; dc-tier error-feedback
          residuals CARRY across the swap, disabling drains the
          in-flight aggregate first so no gradient is lost; the
          collective-consistency audit re-runs on the new program
          before it is installed when GEOMX_AUDIT is armed).

        Relay decisions are host-plane only and never reach this
        method (``ControlActuator`` routes them to the transport).
        """
        if not self._control:
            raise ValueError(
                "apply_control needs GEOMX_CONTROL/GeoConfig(control="
                "True): the compiled step carries no control operands")
        kind = getattr(decision, "kind", None)
        if kind == "ratio":
            return self._apply_ratio(state, decision)
        if kind == "depth":
            return self._apply_depth(state, decision)
        raise ValueError(f"unknown control decision kind {kind!r}; "
                         "apply_control handles ratio | depth")

    def _apply_ratio(self, state: TrainState, decision) -> TrainState:
        from geomx_tpu.control.actuators import CONTROL_KEY
        comp = self._dc_ratio_compressor()
        if comp is None:
            raise ValueError(
                "ratio decision with no ratio-bearing dc compressor: "
                "configure bsc/mpq compression (the control scale tunes "
                "the top-k ratio)")
        target = float(decision.value)
        # the configured ratio is the wire CAPACITY — the traced scale
        # only selects below it (static shapes never change)
        scale = min(max(target / float(comp.ratio), 1e-6), 1.0)
        ctl = state.sync_state[CONTROL_KEY]
        leaf = ctl["bsc_ratio_scale"]
        new_leaf = jax.device_put(
            jnp.full(leaf.shape, scale, leaf.dtype), leaf.sharding)
        new_ctl = dict(ctl, bsc_ratio_scale=new_leaf)
        return TrainState(
            step=state.step, params=state.params,
            opt_state=state.opt_state, model_state=state.model_state,
            sync_state=dict(state.sync_state, **{CONTROL_KEY: new_ctl}))

    def _apply_depth(self, state: TrainState, decision) -> TrainState:
        import copy

        from geomx_tpu.control.actuators import CONTROL_KEY
        from geomx_tpu.sync.pipeline import PipelinedSync
        target = int(decision.value)
        if target not in (0, 1):
            raise ValueError(f"depth decision value must be 0 or 1 "
                             f"(got {decision.value!r})")
        current = self.control_depth()
        if target == current:
            return state
        if self._zero_plan is not None or self._mgps is not None:
            raise ValueError(
                "depth switching does not compose with GEOMX_ZERO/"
                "GEOMX_MULTI_GPS: their sharded updates re-layout the "
                "sync state this transition carries; pin the depth "
                "statically instead")
        if self.topology.num_parties <= 1:
            import warnings
            warnings.warn("depth decision ignored: num_parties=1 has "
                          "no dc-tier collective to pipeline",
                          RuntimeWarning, stacklevel=2)
            return state
        if target == 0:
            # land the in-flight aggregate BEFORE the swap: the parked
            # gradient applies exactly once, nothing is lost
            state = self.drain_pipeline(state)
        params0 = unreplicate_tree(state.params)
        ms0 = unreplicate_tree(state.model_state)
        old_ss = dict(unreplicate_tree(state.sync_state))
        ctl = old_ss.pop(CONTROL_KEY)
        if target == 1:
            new_sync = PipelinedSync(
                self.sync, dcasgd_lambda=self.config.pipeline_dcasgd)
        else:
            new_sync = copy.copy(self.sync.inner)
            # unwrap the PipelinedCompressor installed at wrap time; the
            # BucketedCompressor underneath (and its layout cache) is
            # shared, so no re-trace of the bucket layout
            new_sync.dc_compressor = self.sync.inner.dc_compressor.inner
        new_sync.bind_topology(self.topology)
        if self._membership is not None:
            new_sync.bind_membership(self._membership)
        # state transition with EF carry: the dc-tier error-feedback
        # residuals live at the same bucket coordinates on both sides of
        # the swap — discarding them would replay the parked mass as a
        # one-off gradient spike
        fresh = new_sync.init_state(params0, model_state=ms0)
        if target == 1:
            inner_fresh = dict(fresh["inner"])
            for key, val in old_ss.items():
                if key == "dc_comp":
                    inner_fresh["dc_comp"] = dict(
                        inner_fresh["dc_comp"], inner=val)
                elif key in inner_fresh:
                    inner_fresh[key] = val
            fresh = dict(fresh, inner=inner_fresh)
        else:
            old_inner = old_ss["inner"]
            fresh = dict(fresh)
            for key, val in old_inner.items():
                if key == "dc_comp":
                    fresh["dc_comp"] = val["inner"]
                elif key in fresh:
                    fresh[key] = val
        fresh[CONTROL_KEY] = ctl
        cache_key = (target, self._membership)
        step_fn = self._control_cache.get(cache_key)
        if step_fn is None:
            step_fn = build_train_step(
                self.loss_fn, self.tx, new_sync, self.topology,
                self.mesh, donate=self._donate, config=self.config,
                sp_model=self._sp_model)
            self._control_cache[cache_key] = step_fn
        new_state = TrainState(
            step=state.step, params=state.params,
            opt_state=state.opt_state, model_state=state.model_state,
            sync_state=replicate_tree(fresh, self.topology, self.mesh))
        # collective-signature audit across the swap (analysis/): the
        # new program's own cross-party consistency findings gate BEFORE
        # it is installed — a depth change legitimately changes the
        # collective sequence, so the diff-vs-reference check is
        # re-ARMED on the new program rather than diffed across depths
        self._step_args = None  # the next fit records the new structure
        if self._audit and self._audit_args is not None:
            _, xb_s, yb_s = self._audit_args
            self._audit_args = (jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                new_state), xb_s, yb_s)
            sig, findings = self._step_signature(step_fn)
            from geomx_tpu.analysis import enforce
            leftover = enforce(list(findings), self._audit_gate)
            if leftover:
                import warnings
                warnings.warn("\n".join(f.format() for f in leftover),
                              RuntimeWarning, stacklevel=2)
            self._audit_sigs = {self._membership: (sig, findings)}
        # install: the new sync owns the dc compressor stack from here;
        # membership/epoch caches built against the old program drop
        self.sync = new_sync
        self.train_step = step_fn
        self.config = dataclasses.replace(self.config,
                                          pipeline_depth=target)
        self._step_cache = {self._membership: step_fn}
        self._epoch_runners.clear()
        self._drain_step = None
        return new_state

    def catchup_payload(self, state: TrainState) -> bytes:
        """The re-admission catch-up blob: one unreplicated copy of the
        full TrainState (params, optimizer, model state AND sync state),
        serialized in the checkpoint tree format — what the surviving
        parties broadcast to a returning party before
        ``apply_membership`` widens the collective back over it.  Under
        ZeRO the shard-bearing fields keep the full worker axis (shard
        content differs per worker slot by design; copy (0, 0) would
        hand the returning party W copies of worker 0's shard)."""
        from geomx_tpu.resilience.liveness import pack_catchup
        if self._zero_plan is not None:
            from geomx_tpu.train.zero import host_zero_state
            return pack_catchup(host_zero_state(state))
        return pack_catchup(TrainState(
            step=np.asarray(jax.device_get(state.step)),
            params=unreplicate_tree(state.params),
            opt_state=unreplicate_tree(state.opt_state),
            model_state=unreplicate_tree(state.model_state),
            sync_state=unreplicate_tree(state.sync_state)))

    def admit_party(self, payload: bytes) -> TrainState:
        """Install a catch-up payload as this process's authoritative
        state (the returning party's half of the protocol): the inverse
        of :meth:`catchup_payload`, re-replicated with the same
        placement ``init_state`` uses (shard-aware under ZeRO)."""
        from jax.sharding import NamedSharding, PartitionSpec
        from geomx_tpu.resilience.liveness import unpack_catchup
        t = unpack_catchup(payload)
        if self._zero_plan is not None:
            from geomx_tpu.train.zero import place_zero_state
            return place_zero_state(t, self.topology, self.mesh)
        return TrainState(
            step=jax.device_put(jnp.asarray(t.step),
                                NamedSharding(self.mesh, PartitionSpec())),
            params=replicate_tree(t.params, self.topology, self.mesh),
            opt_state=replicate_tree(t.opt_state, self.topology, self.mesh),
            model_state=replicate_tree(t.model_state, self.topology,
                                       self.mesh),
            sync_state=replicate_tree(t.sync_state, self.topology,
                                      self.mesh))

    # ---- checkpointing (sharded-state aware; docs/api.md) ------------------

    def checkpoint_meta(self) -> dict:
        """The meta block a checkpoint of this trainer's state carries:
        whether the state is ZeRO-sharded and the topology it was
        sharded over, so :meth:`load_checkpoint` can re-shard onto a
        different worker count and reject a GEOMX_ZERO mismatch."""
        from geomx_tpu.train.zero import zero_checkpoint_meta
        return zero_checkpoint_meta(self._zero_plan, self.topology)

    def save_checkpoint(self, path: str, state: TrainState,
                        step=None) -> str:
        """Save ``state`` with this trainer's layout meta.  The device
        arrays keep their full ``[P, W, ...]`` replica axes, so a
        ZeRO run's per-worker shards are all captured (restoring onto
        the same topology is bit-exact, including mid-pipeline
        buffers)."""
        from geomx_tpu.utils.checkpoint import save_checkpoint
        return save_checkpoint(path, state, step=step,
                               meta=self.checkpoint_meta())

    def load_checkpoint(self, path: str, template: TrainState) -> TrainState:
        """Restore a checkpoint into this trainer.

        ``template`` is a state with this trainer's structure and
        placements (fresh ``init_state`` output).  Rules:

        - the checkpoint's ZeRO flag must match this trainer's
          ``GEOMX_ZERO`` — a sharded optimizer cannot be installed into
          a replicated update (or vice versa) and the mismatch raises
          with the fix spelled out;
        - same topology: leaves re-place directly (bit-exact resume,
          mid-pipeline buffers included);
        - different worker count (e.g. saved on 2x4, restored onto
          2x2): shard-bearing leaves are gathered into full flat
          buckets and re-split for the new worker axis
          (train/zero.py ``reshard_zero_state``)."""
        from geomx_tpu.utils.checkpoint import load_checkpoint
        host_state, meta = load_checkpoint(path, with_meta=True)
        ck_zero = bool((meta or {}).get("zero", False))
        if ck_zero != (self._zero_plan is not None):
            have = "GEOMX_ZERO=1" if ck_zero else "GEOMX_ZERO=0 (replicated)"
            want = "GEOMX_ZERO=1" if self._zero_plan is not None \
                else "GEOMX_ZERO=0 (replicated)"
            raise ValueError(
                f"checkpoint at {path!r} was saved with {have} but this "
                f"trainer runs {want}: the optimizer-state layouts are "
                "incompatible (sharded flat buckets vs replicated "
                "leaves).  Restore with a matching GEOMX_ZERO setting, "
                "or re-save from a trainer in the target mode")
        topo_meta = (int((meta or {}).get("num_parties",
                                          self.topology.num_parties)),
                     int((meta or {}).get("workers_per_party",
                                          self.topology.workers_per_party)))
        here = (self.topology.num_parties, self.topology.workers_per_party)
        if not ck_zero or topo_meta == here:
            # same layout: direct re-placement onto the template's
            # shardings (bit-exact)
            from geomx_tpu.utils.checkpoint import place_like
            return place_like(host_state, template)
        from geomx_tpu.train.zero import reshard_zero_state
        return reshard_zero_state(host_state, template, self.mesh)

    def drain_pipeline(self, state: TrainState) -> TrainState:
        """Apply a pipelined sync algorithm's completed in-flight dc-tier
        aggregate without feeding a new batch (sync/pipeline.py): with
        ``GEOMX_PIPELINE_DEPTH=1`` the last launched collectives have not
        been applied when training stops — call this after the final
        ``fit`` (before export/eval) so the last batch's gradient AND its
        model-state (BatchNorm) aggregate land.  The mirror of the
        pipeline's warmup bubble (the first step applies a zero aggregate
        while the buffer fills).  No-op for synchronous algorithms; the
        drained gradient buffer is zeroed (a subsequent ``fit`` warms up
        again) and the model-state buffer keeps the applied value, the
        same seeding a fresh init gets."""
        sync = self.sync
        if not hasattr(sync, "drain_grads"):
            return state
        if self._drain_step is None:
            from geomx_tpu.parallel.collectives import shard_map_compat
            from geomx_tpu.topology import WORKER_AXIS
            from geomx_tpu.train.state import state_specs
            tx = self.tx
            zplan = self._zero_plan

            def _drain(st):
                def squeeze(t):
                    return jax.tree.map(lambda a: a[0, 0], t)

                def expand(t):
                    return jax.tree.map(lambda a: a[None, None], t)
                params = squeeze(st.params)
                opt_state = squeeze(st.opt_state)
                model_state = squeeze(st.model_state)
                sync_state = squeeze(st.sync_state)
                if zplan is not None:
                    # ZeRO drain: apply the parked shard aggregates to
                    # this worker's param shards, then the same
                    # all_gather the step runs rebuilds full params —
                    # the buffers hold reduced values, so the gather is
                    # the drain's only collective
                    g_sh, sync_state = sync.drain_grad_shards(params,
                                                              sync_state)
                    params, opt_state = zplan.apply_shard_update(
                        tx, g_sh, params, opt_state, WORKER_AXIS)
                else:
                    # no collectives: the buffers already hold reduced
                    # values
                    g, sync_state = sync.drain_grads(params, sync_state)
                    updates, opt_state = tx.update(g, opt_state, params)
                    params = optax.apply_updates(params, updates)
                model_state, sync_state = sync.drain_model_state(
                    model_state, sync_state)
                return TrainState(step=st.step, params=expand(params),
                                  opt_state=expand(opt_state),
                                  model_state=expand(model_state),
                                  sync_state=expand(sync_state))

            specs = state_specs()
            self._drain_step = jax.jit(shard_map_compat(
                _drain, self.mesh, in_specs=(specs,), out_specs=specs))
        return self._drain_step(state)

    def _publish_telemetry(self, telem: dict, iteration: int,
                           stacked: bool = False) -> None:
        """Publish one step's probe dict (already device_get) to the
        metric registry + event log.  ``stacked=True``: the values carry
        a leading scan dimension (epoch runner) — publish the last step.
        Scalars become ``geomx_step_probe{probe=...}`` gauges, per-party
        vectors ``geomx_step_probe_party{probe=...,party=...}``; the
        static wire accounting also feeds monotonic byte/step counters
        (delta-scaled by the steps since the last publish, so counter
        rates stay honest at any log_every)."""
        from geomx_tpu.telemetry import get_registry, log_event
        reg = get_registry()
        fam = reg.gauge("geomx_step_probe",
                        "Latest published in-graph step probe", ("probe",))
        fam_p = reg.gauge("geomx_step_probe_party",
                          "Latest per-party in-graph step probe",
                          ("probe", "party"))
        flat: dict = {}
        for name, val in telem.items():
            arr = np.asarray(val)
            if stacked and arr.ndim >= 1:
                arr = arr[-1]
            if arr.ndim == 0:
                flat[name] = float(arr)
                fam.labels(probe=name).set(float(arr))
            elif arr.ndim == 1:
                flat[name] = [float(v) for v in arr]
                for p, v in enumerate(arr):
                    fam_p.labels(probe=name, party=str(p)).set(float(v))
        if self.loop_stats is not None:
            fam_l = reg.gauge("geomx_fit_phase_seconds",
                              "Seconds the running fit's host loop has "
                              "spent in each phase", ("phase",))
            for phase, rec in self.loop_stats.phases.items():
                fam_l.labels(phase=phase).set(rec["total_s"])
            fam_l.labels(phase=layers.DRAINED).set(
                self.loop_stats.drained["total_s"])
        steps = iteration - self._telem_last_it
        if steps > 0:
            reg.counter("geomx_train_steps_total",
                        "Training steps published").inc(steps)
            if "dc_wire_bytes" in flat:
                reg.counter(
                    "geomx_dc_wire_bytes_total",
                    "dc-tier bytes put on the wire per party"
                ).inc(flat["dc_wire_bytes"] * steps)
            self._telem_last_it = iteration
        dc = getattr(self.sync, "dc_compressor", None)
        if dc is None:  # PipelinedSync wraps the algorithm that has it
            dc = getattr(getattr(self.sync, "inner", None),
                         "dc_compressor", None)
        while dc is not None and not hasattr(dc, "layout_summary") \
                and hasattr(dc, "inner"):
            dc = dc.inner  # unwrap Pipelined/DGT wrappers to the bucketer
        layout = getattr(dc, "layout_summary", None)
        layout = layout() if callable(layout) else None
        if layout:
            reg.gauge("geomx_bucket_count",
                      "dc-tier fused buckets per step").set(
                layout["num_buckets"])
            reg.gauge("geomx_bucket_pad_fraction",
                      "Lane-padding waste in the bucket layout").set(
                layout["pad_fraction"])
            if self._zero_plan is not None:
                # ZeRO bucket-shard layout: what one chip actually owns
                # (the memory claim's denominator)
                w = self._zero_plan.W
                reg.gauge("geomx_zero_workers",
                          "Worker-axis width the weight update is "
                          "sharded over").set(w)
                reg.gauge("geomx_zero_shard_elems",
                          "Flat bucket elements owned per chip under "
                          "the ZeRO-sharded update").set(
                    layout["padded_elems"] / w)
        if self._zero_plan is not None:
            reg.gauge("geomx_zero_enabled",
                      "1 when the ZeRO-sharded weight update is "
                      "active").set(1.0)
        if self._event_log is not None:
            self._event_log.emit("step_probes", iteration=iteration,
                                 **flat)
        else:
            log_event("step_probes", iteration=iteration, **flat)
        if self._capsule is not None:
            # record the sensor surface the way a control tick reads it
            # (registry gauge families) — what makes the capsule's
            # replayed observation stream bit-identical to the live one
            self._capsule.record_step(iteration)
        if self._flight is not None:
            fired = self._flight.record(
                iteration, flat,
                membership_version=self._membership_version,
                phases=self._attribution_phases())
            if fired:
                ev = dict(iteration=iteration, fired=fired,
                          bundle=(self._flight.dumps[-1]
                                  if self._flight.dumps else None))
                if self._event_log is not None:
                    self._event_log.emit("flight_anomaly", **ev)
                else:
                    log_event("flight_anomaly", **ev)

    def _attribution_phases(self) -> Optional[dict]:
        """Phase-fraction summary of the ``train/step`` spans the host
        profiler recorded since the previous publish boundary (None when
        the profiler is off or no step span landed in the window) — the
        ``phases`` feed the flight recorder's exposed_comms_jump rule
        watches.  Advances the window mark so consecutive publishes see
        disjoint span windows."""
        from geomx_tpu.utils.profiler import get_profiler
        prof = get_profiler()
        if not prof.running:
            return None
        from geomx_tpu.telemetry.attribution import attribute_trace
        att = attribute_trace(prof.to_doc(), since_us=self._attr_window_us)
        self._attr_window_us = prof.now_us()
        if not att["num_steps"]:
            return None
        return att["summary"]

    def _compiled_step(self, state, xb, yb):
        """The active step program, compiled ahead of time for these
        arguments (arrays or ``jax.ShapeDtypeStruct`` trees); a program
        the step already ran comes from the compile caches."""
        return self.train_step.lower(state, xb, yb).compile()

    def step_layers(self, state, xb, yb) -> dict:
        """The program's table of its own step: ``{"ops": {instruction
        name: OpLayer}, "instructions", "unscoped", "unnamed", "seconds"}``
        from the compiled step's HLO text (telemetry/layers.op_layers).
        A profile names device events by instruction, so the table
        charges each to a scope of the vocabulary and a layer.
        ``unscoped`` counts the instructions that carry no scope of the
        vocabulary; ``unnamed`` those among them that carry no op name at
        all, which the compiler made (copies, layout changes).  Takes
        ``last_step_signature()`` as gladly as arrays, so a fresh trainer
        can produce it after the timed work."""
        begin = time.perf_counter()
        ops = layers.op_layers(self._compiled_step(state, xb, yb).as_text())
        return {"ops": ops, "instructions": len(ops),
                "unscoped": sum(1 for v in ops.values() if not v.scope),
                "unnamed": sum(1 for v in ops.values() if v.scope is None),
                "seconds": time.perf_counter() - begin}

    def _first_boundary(self, step: int) -> None:
        """The trainer's first step has its results on the host."""
        self.lifecycle.first_boundary(step)
        self.publish_memory_metrics()

    def publish_memory_metrics(self) -> None:
        """Set the per-chip step-memory gauges
        (``geomx_step_memory_bytes{component}``) from the lifecycle
        record: the state's classes from the placed arrays the first step
        got; ``compiled_step`` from what the allocator reserved between
        that dispatch and its results (the loaded program's scratch
        space), left out until then and where the backend has no
        allocator statistics.  ``fit`` calls it at both points; it does
        nothing with telemetry off, and nothing is lowered or compiled
        for it."""
        if not self._telemetry:
            return
        from geomx_tpu.telemetry import get_registry
        fam = get_registry().gauge(
            "geomx_step_memory_bytes",
            "Per-chip training-step memory by component", ("component",))
        for component, value in self.lifecycle.state_bytes.items():
            fam.labels(component=component).set(float(value))
        reserved = self.lifecycle.step_reserved_bytes()
        if reserved is not None:
            fam.labels(component="compiled_step").set(float(reserved))

    def predict_logits(self, state: TrainState, x: np.ndarray,
                       batch_size: int = 512) -> np.ndarray:
        """Jitted logits over a host array (one device, unreplicated
        params); the single eval path Module.predict/score also use."""
        params = jax.tree.map(lambda a: a[0, 0], state.params)
        model_state = jax.tree.map(lambda a: a[0, 0], state.model_state)
        outs = []
        for i in range(0, len(x), batch_size):
            xb = x[i:i + batch_size]
            pad = batch_size - len(xb)
            if pad:  # pad the ragged tail: one compiled shape only
                xb = np.concatenate(
                    [xb, np.zeros((pad,) + xb.shape[1:], xb.dtype)])
            logits = np.asarray(self._logits_fn(params, model_state,
                                                jnp.asarray(xb)))
            outs.append(logits[:batch_size - pad] if pad else logits)
        return np.concatenate(outs) if outs else np.zeros((0,))

    def evaluate(self, state: TrainState, x: np.ndarray, y: np.ndarray,
                 batch_size: int = 512) -> float:
        """Test accuracy over (x, y): the dataset is cached on device on
        first use and the whole sweep runs as ONE scanned program — one
        dispatch and one scalar readback per call, instead of a host
        round trip per batch."""
        n = len(x)
        # content-fingerprint cache key (not object identity, which a
        # recycled id or in-place mutation would silently go stale on):
        # all of y plus x strided down to <= ~4 MB.  A mutation confined
        # to skipped x elements can evade the fingerprint; per-epoch eval
        # sets are static in practice.
        import hashlib
        xa, ya = np.ascontiguousarray(x), np.ascontiguousarray(y)
        stride = max(1, xa.nbytes // (4 << 20))
        fp = hashlib.md5(xa[::stride].tobytes() + ya.tobytes()).hexdigest()
        cache_key = (xa.shape, fp, batch_size)
        cached = self._eval_cache.get(cache_key)
        if cached is None:
            pad = (-n) % batch_size
            xp = np.concatenate(
                [xa, np.zeros((pad,) + xa.shape[1:], xa.dtype)]) \
                if pad else xa
            yp = np.concatenate(
                [ya, np.full((pad,), -1, ya.dtype)]) if pad else ya
            cached = (jax.device_put(xp), jax.device_put(yp))
            if len(self._eval_cache) >= 2:  # 2-slot LRU: train+test sets
                self._eval_cache.pop(next(iter(self._eval_cache)))
            self._eval_cache[cache_key] = cached
        else:  # refresh LRU order
            self._eval_cache[cache_key] = self._eval_cache.pop(cache_key)
        dx, dy = cached

        run = self._eval_sweeps.get(batch_size)
        if run is None:
            eval_step = self.eval_step
            b = batch_size

            @jax.jit
            def run(params, model_state, dx, dy):
                # copy (0, 0) selection happens IN-program: eager
                # per-leaf slicing was ~2 host dispatches per leaf per
                # call
                params = jax.tree.map(lambda a: a[0, 0], params)
                model_state = jax.tree.map(lambda a: a[0, 0], model_state)

                def body(acc, i):
                    xb = jax.lax.dynamic_slice_in_dim(dx, i * b, b)
                    yb = jax.lax.dynamic_slice_in_dim(dy, i * b, b)
                    c, _ = eval_step(params, model_state, xb, yb)
                    return acc + c, None
                acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.int32),
                                      jnp.arange(dx.shape[0] // b))
                return acc

            self._eval_sweeps[batch_size] = run
        correct = int(run(state.params, state.model_state, dx, dy))
        return correct / max(n, 1)

    def _epoch_runner(self, loader: GeoDataLoader):
        """One-dispatch-per-epoch runner: lax.scan over the epoch's steps
        with on-device batch gather/augment inside the program.  With a
        device-cached dataset this removes every per-step host round trip
        — the strongest form of the input/compute overlap the reference
        builds from engine threads + prefetching iterators.  Cached by
        (augment, pad) — the only loader-dependent trace inputs — so the
        closure never pins a loader (or its HBM dataset) in memory."""
        # honor the loader's x/y split (sp topologies shard x's sequence
        # dim over the sp axis while labels stay on the replica grid);
        # the shardings join the cache key so loaders with different
        # layouts don't share a traced runner
        x_sharding = getattr(loader, "x_sharding", self._batch_sharding)
        y_sharding = getattr(loader, "y_sharding", self._batch_sharding)
        cache_key = (loader.augment, loader.pad, x_sharding, y_sharding)
        run = self._epoch_runners.get(cache_key)
        if run is not None:
            return run
        from geomx_tpu.data.loader import gather_batch
        step_fn = self.train_step
        augment, pad = loader.augment, loader.pad

        import functools

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run(state, dx, dy, sel, key):
            def body(st, inp):
                s, i = inp
                xb, yb = gather_batch(dx, dy, s, jax.random.fold_in(key, i),
                                      augment=augment, pad=pad)
                if x_sharding is not None:
                    xb = jax.lax.with_sharding_constraint(xb, x_sharding)
                    yb = jax.lax.with_sharding_constraint(yb, y_sharding)
                return step_fn(st, xb, yb)
            return jax.lax.scan(body, state,
                                (sel, jnp.arange(sel.shape[0])))

        self._epoch_runners[cache_key] = run
        return run

    def fit(self, state: TrainState, loader: GeoDataLoader, epochs: int = 1,
            eval_data=None, eval_every: int = 0, log_every: int = 0,
            log_fn: Callable[[str], None] = print,
            measure: Optional[Measure] = None, scan_epochs: bool = False):
        """Run the training loop.

        - ``log_every=N``: record/log loss+train_acc every N iterations;
        - ``eval_every=N``: compute test accuracy every N iterations
          (independent of log_every); 0 = evaluate at each epoch end;
        - records accumulate in ``measure`` (a fresh one by default);
        - ``scan_epochs=True`` (requires a device-cached loader) runs each
          epoch as one scanned device program: per-iteration logging
          coarsens to per-epoch (mean loss/acc over the epoch), eval still
          runs between epochs.

        Pipelined sync (``GEOMX_PIPELINE_DEPTH=1``): the first step from
        a fresh state is the warmup bubble (a zero aggregate applies
        while the pipeline fills) and one aggregate stays in flight when
        fit returns — call ``drain_pipeline`` after the final fit to land
        it.  Both the bubble and the in-flight buffer live in
        ``sync_state``, so a checkpointed run resumes mid-pipeline with
        no re-warmup.

        Returns (state, list of record dicts).
        """
        # no wrapper around the loop: one more Python frame under the
        # step's first call costs JAX's trace and lowering of a BERT-large
        # step 0.6 s (PERF.md section 6, PR 36)
        try:
            measure = measure if measure is not None else Measure()
            measure.reset_clock()
            # iteration numbering restarts per fit, so the telemetry delta
            # base must too — a stale high-water mark from a previous fit
            # would silently swallow this fit's step/byte counter increments
            self._telem_last_it = 0
            # step-time attribution windows restart per fit too: mark the
            # trace clock now so a long-lived process whose global profiler
            # accumulated spans across earlier fits (or other profiled work)
            # attributes only THIS fit's steps — both for the fit-end
            # geomx_phase_fraction summary and the per-publish flight windows
            from geomx_tpu.utils.profiler import get_profiler
            prof = get_profiler()
            fit_since_us = prof.now_us() if prof.running else None
            self._attr_window_us = fit_since_us
            stats = self.loop_stats = layers.LoopStats()
            life = self.lifecycle
            n_devices = self.mesh.devices.size
            layers.record_fit(stats, self._step_args, life)
            if scan_epochs:
                if not getattr(loader, "device_cache", False):
                    raise ValueError("scan_epochs requires device_cache=True "
                                     "on the loader")
                run = self._epoch_runner(loader)
                it = 0
                for epoch in range(epochs):
                    # one dispatch an epoch: the phases are per epoch here,
                    # and `step` is the epoch's first iteration
                    stats.step = it
                    with stats.phase("fit/next_batch"):
                        sel, key = loader.epoch_indices(epoch)
                    with stats.phase("fit/dispatch"):
                        if life.first_dispatch_due:
                            with life.first_dispatch(state, n_devices, it):
                                state, ms = run(state, loader._dev_x,
                                                loader._dev_y, sel, key)
                            self.publish_memory_metrics()
                        else:
                            state, ms = run(state, loader._dev_x, loader._dev_y,
                                            sel, key)
                    it += loader.steps_per_epoch
                    stats.steps = it
                    fields = {}
                    if log_every:
                        with stats.phase("fit/log_sync"):
                            ms = jax.device_get(ms)
                        if life.first_boundary_due:
                            self._first_boundary(it)
                        fields.update(
                            loss=float(np.mean(ms["loss"])),
                            train_acc=float(np.mean(ms["accuracy"])))
                        if self._telemetry and "telemetry" in ms:
                            # scanned epoch: probe values carry a leading
                            # step dimension; publish the last step's
                            self._publish_telemetry(ms["telemetry"], it,
                                                    stacked=True)
                    elif self._telemetry:
                        # log_every=0: still publish the epoch's last step
                        # (same fallback the non-scanned loop has)
                        ms = jax.device_get(ms)
                        if "telemetry" in ms:
                            self._publish_telemetry(ms["telemetry"], it,
                                                    stacked=True)
                    if eval_data is not None:
                        with stats.phase("fit/eval"):
                            fields["test_acc"] = self.evaluate(state,
                                                               *eval_data)
                    if fields:
                        rec = measure.add(epoch=epoch, iteration=it, **fields)
                        with stats.phase("fit/log_fn"):
                            log_fn(json.dumps(rec))
                with stats.phase("fit/log_sync"):
                    jax.block_until_ready(state.step)
                if life.first_boundary_due:
                    self._first_boundary(it)
                self._capsule_checkpoint(prof)
                return state, measure.records
            # Virtual CPU meshes deadlock XLA's collective rendezvous with more
            # than a few in-flight async programs, so there we consume metrics
            # every step.  On a real accelerator that blocking device_get would
            # serialize host work into the step time and cap MFU; instead let
            # XLA's async dispatch run ahead and only sync on log/eval
            # boundaries (bounded every `sync_every` steps as a backstop).
            on_cpu = jax.devices()[0].platform == "cpu"
            sync_every = 1 if on_cpu else max(1, log_every or 32)
            it = 0
            # Host spans and always-on counters of the loop
            # (telemetry/layers.py).  Each iteration is a train/step span
            # holding the phases fit/dispatch, fit/log_sync, fit/eval and
            # fit/log_fn; the wait for its batch, fit/next_batch, comes just
            # before it (an epoch's last wait finds no batch and is no step).
            # All carry the same `step`.  The spans reach a jax.profiler
            # session always and the Chrome trace when the host profiler
            # runs; LoopStats needs neither.  train/compute brackets dispatch
            # and boundary wait for attribute_trace: with async dispatch that
            # is host time only, and the device's share of it is the
            # boundary wait (on the CPU backend, which syncs every step, it
            # is the real step).  The kernel- and comm-category spans the
            # step's own code opens are entered while jit TRACES it, once a
            # compile: on the host they time tracing, not compute or
            # communication.
            for epoch in range(epochs):
                batches = iter(loader.epoch(epoch, prefetch=self._prefetch))
                while True:
                    stats.step = it
                    with stats.phase("fit/next_batch"):
                        batch = next(batches, None)
                    if batch is None:
                        break
                    xb, yb = batch
                    if self._step_args is None:
                        layers.record_fit(
                            stats, self._abstract_step_args(state, xb, yb), life)
                    # arm the auditor on the first batch (abstract trace of
                    # the active program; no-op unless GEOMX_AUDIT is on)
                    self._audit_capture(state, xb, yb)
                    with prof.scope("train/step", "step",
                                    args={"step": it}):
                        with prof.scope("train/compute", "compute"):
                            with stats.phase("fit/dispatch"):
                                if life.first_dispatch_due:
                                    # once a trainer: trace, lower, fetch or
                                    # compile, load and enqueue
                                    with life.first_dispatch(state, n_devices,
                                                             it):
                                        state, metrics = self.train_step(
                                            state, xb, yb)
                                    self.publish_memory_metrics()
                                else:
                                    state, metrics = self.train_step(state, xb,
                                                                     yb)
                            it += 1
                            stats.steps = it
                            # the log/sync boundary wait is device compute
                            # (on the CPU backend the whole step; on an
                            # accelerator the async-dispatch catch-up), so
                            # it stays inside the compute span — attributed
                            # host_stall is then genuinely the input
                            # pipeline and dispatch gaps, which is what
                            # GEOMX_PREFETCH shortens
                            synced = None
                            if log_every and it % log_every == 0:
                                with stats.phase("fit/log_sync"):
                                    synced = jax.device_get(metrics)
                                if life.first_boundary_due:
                                    self._first_boundary(it)
                            elif it % sync_every == 0:
                                with stats.phase("fit/log_sync"):
                                    jax.block_until_ready(metrics["loss"])
                                if life.first_boundary_due:
                                    self._first_boundary(it)
                        fields = {}
                        if synced is not None:
                            metrics = synced
                            fields.update(loss=float(metrics["loss"]),
                                          train_acc=float(metrics["accuracy"]))
                            if self._telemetry and "telemetry" in metrics:
                                self._publish_telemetry(metrics["telemetry"],
                                                        it)
                            if "counters" in metrics:
                                stats.count(metrics["counters"])
                        if eval_data is not None and eval_every \
                                and it % eval_every == 0:
                            with stats.phase("fit/eval"):
                                fields["test_acc"] = self.evaluate(state,
                                                                   *eval_data)
                        if fields:
                            rec = measure.add(epoch=epoch, iteration=it,
                                              **fields)
                            with stats.phase("fit/log_fn"):
                                log_fn(json.dumps(rec))
                if self._telemetry and not log_every and it:
                    # no log boundary ever synced this epoch: publish the
                    # epoch's last step so the registry/event log still track
                    # a log_every=0 run (one device_get per epoch)
                    last = jax.device_get(metrics)
                    if "telemetry" in last:
                        self._publish_telemetry(last["telemetry"], it)
                if eval_data is not None and not eval_every:
                    with stats.phase("fit/eval"):
                        acc = self.evaluate(state, *eval_data)
                    rec = measure.add(epoch=epoch, iteration=it, test_acc=acc)
                    with stats.phase("fit/log_fn"):
                        log_fn(json.dumps(rec))
            if self._telemetry and prof.running:
                # publish the fit's phase-fraction summary from the step
                # spans recorded above (geomx_phase_fraction gauges)
                from geomx_tpu.telemetry.attribution import (
                    attribute_trace, publish_attribution)
                att = attribute_trace(prof.to_doc(), since_us=fit_since_us)
                if att["num_steps"]:
                    publish_attribution(att["summary"])
            self._capsule_checkpoint(prof)
            return state, measure.records
        finally:
            # also where log_fn left the loop by an exception
            self.lifecycle.mark("fit/end", self.loop_stats.steps
                                if self.loop_stats is not None else 0)

    def _capsule_checkpoint(self, prof) -> None:
        """Refresh the run capsule at a fit boundary: attach the
        latest profiler trace (replacing this rank's previous one) and
        rewrite the archive atomically.  A crash between fits leaves
        the previous complete capsule."""
        if self._capsule is None:
            return
        if prof.running:
            # Profiler() defaults self.rank = None — the getattr
            # fallback alone never applies, hence the `or 0`
            rank = getattr(prof, "rank", None)
            self._capsule.add_trace(prof.to_doc(),
                                    label=f"rank{rank if rank is not None else 0}")
        self._capsule.write()

    def close_capsule(self) -> None:
        """Deterministically finish capsule recording: stop the
        sampler, detach the observatory tap and write the final
        archive.  (A garbage-collected trainer stops its sampler/tap
        via finalizers, but does not write.)"""
        if self._capsule is not None:
            self._capsule.close()
