"""Campaign execution: one fused megabatch dispatch per compiled shape.

The runner walks the planner's megabatch list, memoizing topologies,
workloads and failure states across batches, and executes

  * fast-engine megabatches as a single ``fastsim.simulate_megabatch`` call:
    every member (scheme, load, failure, seed) cell stacks onto one fused,
    jitted batch axis -- padded to the megabatch's bucketed packet shape and,
    when several devices are visible (``Campaign.shard='auto'``),
    ``shard_map``-sharded across them;
  * loop-engine megabatches (ACK/ECN schemes) as a single
    ``loopsim.simulate_megabatch`` call: the scheme/load/failure/seed cells
    of one compiled slotted engine -- plus the ``g_converge`` and rho axes,
    which ride as per-row operands -- fuse the same way.

Each grid point yields one record in the :class:`~repro.sweep.results
.ResultStore`; per-point results are bitwise-identical to standalone
``fastsim.simulate`` calls with the same seeds (tested in
``tests/test_sweep.py``).  Pass ``compile_cache_dir=<dir>`` (or set
``JAX_COMPILATION_CACHE_DIR``) to persist compiled pipelines across
invocations.

Telemetry (``repro.obs``): every run can emit a versioned JSONL dispatch
trace (``trace=TraceWriter(...)``) -- one span per fused dispatch carrying
the member population, padding-fill ratios, device fill, wall seconds and
compile-cache state -- and logs through a :class:`~repro.obs.log
.SweepLogger` (default one line per dispatch).  Both are pure observers:
with them off (the defaults) the runner's outputs are byte-identical to the
pre-telemetry runner (tested in ``tests/test_obs.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..net.topology import FatTree, LinkState, rho_max
from ..net import workloads, fastsim, loopsim
from ..core import lb_schemes as lbs
from ..core.retry import retry_call
from ..faults import FaultSchedule
from ..obs.log import SweepLogger, dispatch_line
from ..obs.probes import probe_shape
from ..obs.stages import collect, stage
from ..obs.trace import TraceWriter
from . import compile_cache
from .planner import MegaBatch, SeedBatch, plan
from .results import ResultStore, loop_point_record, point_record
from .spec import Campaign, FailureSpec, GridPoint, WorkloadSpec


def build_workload(tree: FatTree, load: WorkloadSpec):
    if load.kind == "permutation":
        return workloads.permutation(tree, load.msg_packets,
                                     np.random.default_rng(load.rng_seed),
                                     inter_pod_only=load.inter_pod_only)
    if load.kind == "all_to_all":
        return workloads.all_to_all(tree, load.msg_packets)
    if load.kind == "fsdp_rings":
        return workloads.fsdp_rings(tree, load.gpus_per_server,
                                    load.msg_packets,
                                    np.random.default_rng(load.rng_seed))
    raise ValueError(f"unknown workload kind {load.kind!r}")


def build_links(tree: FatTree,
                failure: Optional[FailureSpec]) -> Optional[LinkState]:
    """The campaign interpretation of a FailureSpec (None = all links up):
    counter-keyed draws by default, the old sequential ``np.random`` stream
    when the spec pins ``legacy_rng``."""
    if failure is None:
        return None
    if failure.legacy_rng:
        return LinkState.random_failures(
            tree, failure.p_fail, np.random.default_rng(failure.rng_seed))
    return LinkState.random_failures(tree, failure.p_fail,
                                     seed=failure.rng_seed)


class _Cache:
    """Memoized topology / workload / failure-state construction."""

    def __init__(self):
        self.trees: Dict[int, FatTree] = {}
        self.wls: Dict[Tuple, object] = {}
        self.cps: Dict[Tuple, object] = {}
        self.links: Dict[Tuple, LinkState] = {}
        self.rhos: Dict[Tuple, float] = {}

    def tree(self, k: int) -> FatTree:
        if k not in self.trees:
            self.trees[k] = FatTree(k)
        return self.trees[k]

    def compiled_phases(self, k: int, load: WorkloadSpec, phase):
        """The ``repro.phases.CompiledPhases`` of a phased point (its fused
        workload plus the per-phase bookkeeping the records need)."""
        key = (k, load, phase)
        if key not in self.cps:
            self.cps[key] = phase.compile(self.tree(k), load.msg_packets,
                                          rng_seed=load.rng_seed)
        return self.cps[key]

    def workload(self, k: int, load: WorkloadSpec, phase=None):
        if phase is not None:
            return self.compiled_phases(k, load, phase).workload
        key = (k, load)
        if key not in self.wls:
            self.wls[key] = build_workload(self.tree(k), load)
        return self.wls[key]

    def link_state(self, k: int,
                   failure: Optional[FailureSpec]) -> Optional[LinkState]:
        """Static link state for FailureSpec rows.  FaultSchedule rows get
        None: the engines compile the schedule's epoch stack themselves."""
        if failure is None or isinstance(failure, FaultSchedule):
            return None
        key = (k, failure)
        if key not in self.links:
            self.links[key] = build_links(self.tree(k), failure)
        return self.links[key]

    def rho_links(self, k: int, failure) -> Optional[LinkState]:
        """The link state ``rho='auto'`` resolves against.  For dynamic
        schedules this is deterministically the *epoch-0* pattern: the
        sending rate is fixed before the collective starts, when only the
        base failure state is observable."""
        if isinstance(failure, FaultSchedule):
            key = (k, failure, "ep0")
            if key not in self.links:
                self.links[key] = failure.compile(self.tree(k)).links[0]
            links = self.links[key]
            return links if links.any_failure() else None
        return self.link_state(k, failure)

    def rho_auto(self, k: int, load: WorkloadSpec, failure,
                 phase=None) -> float:
        key = (k, load, failure, phase)
        if key not in self.rhos:
            links = self.rho_links(k, failure)
            wl = self.workload(k, load, phase)
            self.rhos[key] = (rho_max(self.tree(k), links, wl.flow_src,
                                      wl.flow_dst)
                              if links is not None else 1.0)
        return self.rhos[key]


def _fault_of(b: SeedBatch):
    """The dynamic-schedule item field: the failure itself for FaultSchedule
    rows (the engines compile the epoch stack), None for static rows."""
    return b.failure if isinstance(b.failure, FaultSchedule) else None


def _run_fast_mega(mega: MegaBatch, campaign: Campaign, cache: _Cache):
    """One fused dispatch for all member batches; returns results per member."""
    with stage("prep"):
        items = [(cache.tree(b.k), cache.workload(b.k, b.load, b.phase),
                  lbs.by_name(b.scheme), b.seeds,
                  cache.link_state(b.k, b.failure), _fault_of(b))
                 for b in mega.members]
    n_shards = "auto" if campaign.shard == "auto" else 1
    return fastsim.simulate_megabatch(items, prop_slots=campaign.prop_slots,
                                      backend=campaign.backend,
                                      npk_pad=mega.npk_pad,
                                      n_shards=n_shards, k_pad=mega.k_pad,
                                      probes=campaign.probes)


def _run_loop_mega(mega: MegaBatch, campaign: Campaign, cache: _Cache):
    """One fused loop-engine dispatch for all member batches; rho (possibly
    rho_max under each member's failure pattern) and g_converge are per-row
    operands, so the whole grid slice shares one compiled engine.  Schedule
    rows carry ``g_converge=None`` from the grid (``Campaign.points``):
    their reaction delays come from the schedule itself."""
    rho_opt = campaign.loop_options().get("rho", 1.0)
    items = []
    with stage("prep"):
        for b in mega.members:
            rho = (cache.rho_auto(b.k, b.load, b.failure, b.phase)
                   if rho_opt == "auto" else float(rho_opt))
            items.append((cache.tree(b.k),
                          cache.workload(b.k, b.load, b.phase),
                          lbs.by_name(b.scheme),
                          campaign.loop_config(rho, timing=b.timing),
                          b.seeds, cache.link_state(b.k, b.failure),
                          b.g_converge, _fault_of(b)))
    n_shards = "auto" if campaign.shard == "auto" else 1
    return loopsim.simulate_megabatch(items, npk_pad=mega.npk_pad,
                                      n_shards=n_shards, k_pad=mega.k_pad,
                                      probes=campaign.probes)


def _probe_field(campaign: Campaign):
    stride, samples = probe_shape(campaign.probes)
    return [stride, samples] if samples else None


def _compile_misses() -> int:
    """Total in-process compile-cache misses across both engines; the delta
    around a dispatch distinguishes a fresh compile from a cache hit."""
    return (fastsim._build_run.cache_info().misses
            + loopsim._compiled.cache_info().misses)


def _cache_files(cache_dir) -> int:
    if not cache_dir:
        return 0
    try:
        import pathlib
        return sum(1 for f in pathlib.Path(cache_dir).rglob("*")
                   if f.is_file())
    except OSError:
        return 0


def _dispatch_span(idx: int, mega: MegaBatch, campaign: Campaign,
                   n_shards_pol, devices: int) -> Dict:
    """The deterministic part of a dispatch span: member population and
    padding accounting, computable before execution."""
    rows = mega.n_points
    n_shards = (max(1, min(devices, rows))
                if n_shards_pol == "auto" else 1)
    rows_padded = -(-rows // n_shards) * n_shards
    pkt_rows_real = sum(b.n_packets(b.k) * len(b.seeds)
                        for b in mega.members)
    pkt_rows_padded = rows_padded * mega.npk_pad
    span = {
        "kind": "dispatch",
        "campaign": campaign.name,
        "dispatch": idx,
        "engine": mega.engine,
        "key": repr(mega.key),
        "n_members": len(mega.members),
        "n_points": rows,
        "schemes": sorted({b.scheme for b in mega.members}),
        "trees": sorted({b.k for b in mega.members}),
        "k_pad": mega.k_pad,
        "npk_pad": mega.npk_pad,
        "pkt_rows_real": pkt_rows_real,
        "pkt_rows_padded": pkt_rows_padded,
        "pkt_fill": pkt_rows_real / max(pkt_rows_padded, 1),
        "rows_padded": rows_padded,
        "row_fill": rows / max(rows_padded, 1),
        "n_shards": n_shards,
        "devices": devices,
        "probes": _probe_field(campaign),
    }
    if mega.engine == "loop":
        span["slot_budget"] = int(campaign.max_slots)
        from ..kernels.slot_step import ops as _slot
        span["impl"] = _slot.resolve_impl(campaign.loop_config().impl)
    # Collective-phase members (only-when-set: phase-free campaigns keep
    # byte-identical spans): which schedules ride this dispatch and how
    # many of its fused points are phased.
    phased = [b for b in mega.members if b.phase is not None]
    if phased:
        span["phases"] = sorted({b.phase.label() for b in phased})
        span["phase_points"] = sum(len(b.seeds) for b in phased)
        span["phase_instances"] = max(b.phase.n_instances for b in phased)
    return span


def _point_key(point: GridPoint) -> Tuple:
    """Record-identity tuple of a grid point, matching :func:`_record_key`
    on the record the runner would write for it."""
    tm = point.timing if point.timing is not None else (None, None)
    return (point.campaign, point.k, point.load.label(),
            point.failure.label() if point.failure else None,
            point.scheme, point.seed, point.g_converge,
            int(tm[0]) if tm[0] is not None else None,
            int(tm[1]) if tm[1] is not None else None,
            point.phase.label() if point.phase is not None else None)


def _record_key(rec: Dict) -> Tuple:
    # Fast-engine records carry no g_converge field; .get(None) matches the
    # fast-campaign grid's g_converge=None axis value.  Likewise
    # prop_slots/ack_delay appear only on timing-axis loop records and
    # "phases" only on collective-phase records (pre-phase results.jsonl
    # files resume byte-identically).
    return (rec.get("campaign"), rec.get("k"), rec.get("workload"),
            rec.get("failure"), rec.get("scheme"), rec.get("seed"),
            rec.get("g_converge"), rec.get("prop_slots"),
            rec.get("ack_delay"), rec.get("phases"))


def _run_with_recovery(idx: int, mega: MegaBatch, campaign: Campaign,
                       cache: _Cache, run: Callable, *, retry: int,
                       backoff_s: float, sleep: Callable,
                       log: SweepLogger) -> Tuple[list, List[Dict]]:
    """Execute one fused dispatch with bounded retry and the degradation
    ladder: whole megabatch -> per-member dispatches -> serial per-point.

    Returns (per_member, spans): ``per_member`` aligns with
    ``mega.members``, each entry a per-seed result list in which points
    that failed terminally are None (they yield no records -- the error
    spans are their trace).  ``spans`` are the retry/error/degrade spans
    to emit, in event order.
    """
    spans: List[Dict] = []

    def _base(**kw) -> Dict:
        return {"campaign": campaign.name, "dispatch": idx, **kw}

    def _attempt(fn, stage, **ctx):
        """retry_call around one ladder rung; returns (value, ok)."""
        def on_retry(attempt, e, delay):
            spans.append(_base(kind="retry", stage=stage, attempt=attempt,
                               error=repr(e), backoff_s=delay, **ctx))
            log.info(f"dispatch {idx} [{stage}] attempt {attempt} failed: "
                     f"{e!r}; backing off {delay:.2f}s")
        try:
            return retry_call(fn, max_retries=retry, backoff_s=backoff_s,
                              sleep=sleep, on_retry=on_retry), True
        except Exception as e:  # noqa: BLE001 -- degrade, don't die
            spans.append(_base(kind="error", stage=stage, error=repr(e),
                               **ctx))
            log.info(f"dispatch {idx} [{stage}] failed terminally: {e!r}")
            return None, False

    out, ok = _attempt(lambda: run(mega, campaign, cache), "megabatch")
    if ok:
        return out, spans

    # Rung 2: one dispatch per member batch (halves the blast radius of a
    # compile/OOM failure: a poisoned member no longer sinks its siblings).
    per_member: list = []
    for m, b in enumerate(mega.members):
        sub = MegaBatch(key=mega.key, members=[b])
        out, ok = _attempt(lambda sub=sub: run(sub, campaign, cache)[0],
                           "member", member=m, scheme=b.scheme)
        if ok:
            spans.append(_base(kind="degrade", stage="member", member=m,
                               scheme=b.scheme))
            per_member.append(out)
            continue
        # Rung 3: serial per-point; surviving seeds still record.
        results = []
        for s in b.seeds:
            one = MegaBatch(key=mega.key,
                            members=[dataclasses.replace(b, seeds=(s,))])
            res, ok = _attempt(lambda one=one: run(one, campaign, cache)[0][0],
                               "point", member=m, scheme=b.scheme, seed=s)
            results.append(res if ok else None)
        spans.append(_base(kind="degrade", stage="serial", member=m,
                           scheme=b.scheme,
                           failed=sum(r is None for r in results)))
        per_member.append(results)
    return per_member, spans


def _record(mega: MegaBatch, per_member: list, secs: float, cache: _Cache,
            store: ResultStore, full: Optional[Dict]) -> None:
    """Append one dispatch's records to ``store`` (and its raw results to
    ``full``, when kept), in plan order; points that failed terminally have
    no record (their error span is their trace)."""
    to_record = (loop_point_record if mega.engine == "loop"
                 else point_record)
    for batch, results in zip(mega.members, per_member):
        cp = (cache.compiled_phases(batch.k, batch.load, batch.phase)
              if batch.phase is not None else None)
        for point, res in zip(batch.points(), results):
            if res is None:
                continue
            store.append(to_record(point, res, phases=cp))
            if full is not None:
                full[point] = res
        # Apportion the fused dispatch's wall time over members by their
        # share of fused points, so per-scheme timing summaries stay
        # meaningful.
        store.timings.append((batch, secs * len(batch.seeds)
                              / max(mega.n_points, 1)))


def run_campaign(campaign: Campaign, store: Optional[ResultStore] = None,
                 keep_full: bool = False,
                 progress: Optional[Callable[[str], None]] = None,
                 compile_cache_dir: Optional[str] = None,
                 trace: Optional[TraceWriter] = None,
                 log: Optional[SweepLogger] = None,
                 profile_dir: Optional[str] = None,
                 retry: int = 0, backoff_s: float = 0.5,
                 sleep: Callable[[float], None] = time.sleep,
                 resume: bool = False,
                 cost_params=None):
    """Execute a campaign; returns (records, full_results).

    ``records`` is the flat list of per-point dicts (also appended to
    ``store`` when given, in grid-plan order).  ``full_results`` maps
    ``GridPoint -> FastSimResult/LoopSimResult`` when ``keep_full=True``
    (tests and figure code that need raw delivery vectors), else ``{}``.
    ``compile_cache_dir`` enables the persistent JAX compilation cache, so
    repeat invocations skip compiles entirely; ``JAX_COMPILATION_CACHE_DIR``
    takes precedence over it when set (see :mod:`.compile_cache`).  Pass
    ``False`` to turn the cache off even when the env var is set; that
    switches JAX's persistent cache off for the whole process, and it stays
    off after the campaign returns until a later call enables it.

    Observability (all optional, all pure observers):

    * ``trace`` -- a :class:`~repro.obs.trace.TraceWriter`; the runner emits
      one plan span, one span per fused dispatch and one campaign bookend.
      Each dispatch span splits its seconds into the host stages of
      :mod:`repro.obs.stages` (``prep_s``, ``execute_s``, ``fetch_s``,
      ``post_s``, ``retry_s``, ``record_s``) plus
      ``compile_s``, and counts ``bytes_in``, ``bytes_out`` and
      ``jsq_retries``; the plan span carries ``plan_s``.  The same stages
      annotate the profiler's timeline as ``sweep.<stage>`` spans inside
      one ``sweep.dispatch`` span per dispatch.
    * ``log`` -- a :class:`~repro.obs.log.SweepLogger`; defaults to quiet
      when neither ``log`` nor ``progress`` is given.  The legacy
      ``progress`` callable maps to a debug-level logger with ``progress``
      as its sink, reproducing the old per-member output verbatim.
    * ``profile_dir`` -- wrap execution in ``jax.profiler.trace`` for
      TensorBoard-grade timelines (a backend without a profiler raises).

    Robustness:

    * ``retry`` / ``backoff_s`` -- each dispatch (and each rung of the
      degradation ladder below it) gets ``retry`` extra attempts with
      exponential backoff ``backoff_s * 2**attempt`` before degrading:
      whole megabatch -> per-member dispatches -> serial per-point.  Points
      that fail terminally yield error spans instead of records; the
      campaign keeps going, so callers that need every point compare the
      record count with the plan (the CLI exits non-zero when points are
      missing).  ``sleep`` is injectable for tests.
    * ``resume`` -- treat ``store``'s existing records as a checkpoint:
      dispatches whose full record block is already present are skipped,
      a partially-recorded dispatch is truncated off and re-run whole.
      With a canonical JSONL store the finished file is byte-identical to
      an uninterrupted run's (``tests/test_faults.py``).
    * ``cost_params`` -- a ``sweep.costmodel.CostParams`` for cost-modeled
      campaigns (``Campaign.planner == 'cost'``), e.g. calibrated from a
      measured trace via ``CostParams.from_trace``; ``None`` uses the
      model defaults.  The chosen policy, its predicted cost/fill and the
      rejected alternatives land in the plan span; the campaign bookend
      span carries the realized padded-row fill to compare against.
    """
    if log is None:
        log = (SweepLogger("debug", sink=progress) if progress is not None
               else SweepLogger("quiet"))
    with collect() as planning, stage("plan"):
        if compile_cache_dir is False:
            compile_cache.disable()
            cache_dir = None
        else:
            cache_dir = compile_cache.enable(compile_cache_dir)
        import jax
        devices = len(jax.devices())
        p = plan(campaign, cost_params=cost_params)
        log.info(p.describe())
        if cache_dir:
            log.info(f"persistent compile cache: {cache_dir}")
        cache_files0 = _cache_files(cache_dir)
    if trace:
        span = {
            "kind": "plan", "campaign": campaign.name,
            "n_points": p.n_points, "n_dispatches": p.n_dispatches,
            "n_shapes": p.n_shapes, "devices": devices,
            "engine": campaign.engine, "shard": campaign.shard,
            "probes": _probe_field(campaign),
            "cache_dir": str(cache_dir) if cache_dir else None,
            "plan_s": planning["plan_s"],
        }
        if any(ph is not None for ph in campaign.phases):
            span["phases"] = [ph.label() if ph is not None else None
                              for ph in campaign.phases]
        if p.policy is not None:
            # Cost-modeled planning: the chosen policy, its predicted
            # cost/fill, and the rejected alternatives -- the prediction
            # the campaign bookend's realized fill is compared against.
            span["planner"] = "cost"
            span["policy"] = p.policy.label
            span["kmap"] = [list(kv) for kv in p.policy.kmap]
            span["pkt_exact"] = list(p.policy.pkt_exact)
            if p.cost is not None:
                span["predicted"] = p.cost.as_dict()
            span["alternatives"] = [
                {"policy": lbl, "cost": c, "pkt_fill": f}
                for (lbl, c, f) in p.alternatives]
            if cost_params is not None:
                span["calibration"] = cost_params.source
        trace.emit(span)
    cache = _Cache()
    store = store if store is not None else ResultStore(None)
    n_before = len(store.records)   # store may be shared across campaigns
    full: Dict = {}

    done = 0                        # dispatches already complete on resume
    if resume:
        # The checkpoint region is this campaign's block of pre-existing
        # records (records of other campaigns sharing the store never match
        # _point_key, which carries the campaign name).  Walk dispatches in
        # plan order; a dispatch counts as complete only if the store holds
        # its *entire* record block, in order, at the expected offset.
        # Everything after the last complete dispatch is truncated off (a
        # partially-recorded dispatch re-runs whole), so the finished file
        # is byte-identical to an uninterrupted run's.
        pos = next((i for i, r in enumerate(store.records)
                    if r.get("campaign") == campaign.name),
                   len(store.records))
        for mega in p.megabatches:
            keys = [_point_key(pt) for b in mega.members
                    for pt in b.points()]
            nxt = pos + len(keys)
            if (nxt <= len(store.records)
                    and all(_record_key(store.records[pos + i]) == kk
                            for i, kk in enumerate(keys))):
                pos, done = nxt, done + 1
            else:
                break
        store.truncate(pos)
        n_before = len(store.records)   # kept prefix is not "new" records
        kept = sum(len(b.seeds) for m in p.megabatches[:done]
                   for b in m.members)
        if trace:
            trace.emit({"kind": "resume", "campaign": campaign.name,
                        "dispatches_kept": done, "records_kept": kept})
        log.info(f"resume: {done}/{p.n_dispatches} dispatches already "
                 f"complete ({len(store.records)} records kept)")

    prof = (jax.profiler.trace(str(profile_dir)) if profile_dir
            else contextlib.nullcontext())

    real_rows = padded_rows = 0     # realized padded-row fill this run
    t0 = time.perf_counter()
    with prof:
        for idx, mega in enumerate(p.megabatches):
            if idx < done:          # resume: records already on disk
                continue
            with jax.profiler.TraceAnnotation("sweep.dispatch",
                                              campaign=campaign.name,
                                              dispatch=idx):
                span = _dispatch_span(idx, mega, campaign, campaign.shard,
                                      devices)
                real_rows += span["pkt_rows_real"]
                padded_rows += span["pkt_rows_padded"]
                run = (_run_loop_mega if mega.engine == "loop"
                       else _run_fast_mega)
                misses0 = _compile_misses()
                with collect() as stages:
                    tb = time.perf_counter()
                    per_member, rspans = _run_with_recovery(
                        idx, mega, campaign, cache, run, retry=retry,
                        backoff_s=backoff_s, sleep=sleep, log=log)
                    span["wall_s"] = secs = time.perf_counter() - tb
                    with stage("record"):
                        _record(mega, per_member, secs, cache, store,
                                full if keep_full else None)
                span.update(stages)
                span["cache"] = ("hit" if _compile_misses() == misses0
                                 else "miss")
                if mega.engine == "loop":
                    acked = [float(r.cct_acked_slots)
                             for results in per_member for r in results
                             if r is not None]
                    span["slots_run"] = int(max(acked)) if acked else 0
                    # Share of the fused row-slots that did work: each
                    # row's own ACK-complete slot over rows x slots run.
                    span["row_slot_fill"] = (
                        sum(acked) / max(mega.n_points * span["slots_run"],
                                         1))
                if trace:
                    for s in rspans:    # retry/error/degrade, event order
                        trace.emit(s)
                    trace.emit(span)
                log.info(dispatch_line(span, p.n_dispatches))
                for batch, t in store.timings[-len(mega.members):]:
                    log.debug(f"  {batch.scheme:>16s} k={batch.k} "
                              f"{batch.load.label():<22s} "
                              f"x{len(batch.seeds)} seeds: {t:.2f}s")
    wall = time.perf_counter() - t0
    if trace:
        trace.emit({
            "kind": "campaign", "campaign": campaign.name,
            "n_points": p.n_points, "n_dispatches": p.n_dispatches,
            # Realized padded-row fill over the dispatches this run
            # executed (resume-skipped dispatches excluded): the
            # measurement the plan span's predicted fill is checked
            # against, and the input --plan-from-trace calibrates on.
            "pkt_rows_real": real_rows,
            "pkt_rows_padded": padded_rows,
            "pkt_fill": real_rows / max(padded_rows, 1),
            "wall_s": wall,
            "cache_entries_added": (_cache_files(cache_dir) - cache_files0
                                    if cache_dir else 0),
            "emit_s": trace.emit_s,
        })
    log.info(f"campaign {campaign.name!r} done in {wall:.2f}s "
             f"({p.n_points} points, {p.n_dispatches} dispatches, "
             f"{p.n_shapes} shapes)")
    return store.records[n_before:], full
