"""ServingTelemetry — request-level observability for the inference engines.

PR 1 made the training loop observable; the serving path was blind: no
spans, no counters, speculative stats in an ad-hoc dict.  This facade is
the serving-side sibling of ``StepTelemetry``, built for the questions a
serving operator actually asks:

- **latency percentiles** (p50/p99 TTFT / TPOT / e2e) — histograms, because
  a counter can only produce a mean and SLOs are percentiles;
- **where a request's time went** — per-request lifecycle spans
  (queue_wait → prefill → decode) on one Perfetto track per request,
  next to the engine's dispatch spans on track 0;
- **is the KV pool the bottleneck** — blocks used/free, internal
  fragmentation of allocated pages, and allocation-failure counters per
  decision site (the baseline a radix prefix cache has to beat);
- **why is speculative decoding slow** — accepted/proposed tokens and
  the fused dispatch's wall time, replacing ``eng.spec_stats`` (the
  draft / verify split is read in a device trace, from the ``draft`` and
  ``verify`` scopes inside the one fused program).

One instance per engine with its OWN ``MetricRegistry`` by default (two
engines in one process — the bench runs seven — must not blend their
accept ratios); pass ``registry=telemetry.default_registry`` to fold the
serving series into the process-wide scrape instead.

Timestamps: request lifecycle times are ``time.perf_counter()`` seconds
(callers may substitute a fake clock for deterministic tests); spans
convert through the tracer's epoch so request tracks line up with
dispatch spans in one trace.
"""

from __future__ import annotations

import os
import time
from typing import Dict, Optional

from deepspeed_tpu.config import DeepSpeedConfigModel
from deepspeed_tpu.telemetry.exporter import SnapshotExporter
from deepspeed_tpu.telemetry.registry import MetricRegistry
from deepspeed_tpu.telemetry.tracer import SpanTracer, TraceEmitter

class ServingTelemetryConfig(DeepSpeedConfigModel):
    """``telemetry`` block of the inference engine configs.

    ``enabled`` covers counters/gauges/histograms (a few dict updates and
    ``perf_counter`` reads per DISPATCH, not per token — cheap enough to
    default on).  ``trace_enabled`` adds span recording (bounded buffer).
    ``stream_sync`` blocks on each dispatch's output before timestamping —
    the streaming-server behavior that makes TTFT/TPOT reflect device
    completion instead of host submission; it serializes the dispatch
    pipeline, so it defaults off and the open-loop bench harness turns it
    on explicitly."""

    enabled: bool = True
    trace_enabled: bool = True
    max_trace_events: int = 100_000
    stream_sync: bool = False
    # fleet mode (serving/fleet.py): the replica's name, threaded as a
    # ``replica`` label into EVERY serving metric family so N replicas can
    # share one fleet-level registry without blending their series; None
    # (single-engine default) adds no label, keeping the series names the
    # dashboards already scrape
    replica: Optional[str] = None


class ServingTelemetry:
    def __init__(self, config: Optional[ServingTelemetryConfig] = None,
                 registry: Optional[MetricRegistry] = None,
                 pid: Optional[int] = None):
        cfg = config or ServingTelemetryConfig()
        self.config = cfg
        self.enabled = bool(cfg.enabled)
        self.stream_sync = bool(cfg.stream_sync)
        # fleet mode: one shared registry + a per-replica label on every
        # series (the merge into self.labels below threads it through each
        # write AND each read, so quantile()/value() callers stay oblivious)
        self.labels: Dict[str, str] = (
            {"replica": str(cfg.replica)} if cfg.replica else {})
        self.registry = registry if registry is not None else MetricRegistry()
        if pid is None:
            import jax
            pid = jax.process_index()
        self.tracer = SpanTracer(
            enabled=self.enabled and bool(cfg.trace_enabled), pid=pid,
            max_events=int(cfg.max_trace_events))
        self.emitter = TraceEmitter(process_name="deepspeed_tpu_serving")
        self.exporter = SnapshotExporter(self.registry, self.tracer)
        self._track_count = 0
        # per-request summaries (bounded): histograms answer fleet-level
        # percentile questions, but goodput ("which requests met BOTH their
        # TTFT and TPOT SLOs, and how many tokens did those produce") needs
        # per-request joint attainment — the bench reads this log
        self.request_log: list = []
        self.request_log_cap = 100_000
        self.scan_layers = 0            # set_scan_state
        self.state_kind = "ssm"
        # step-program dispatches of this engine since it was built: the
        # number a dispatch span carries, advanced whether or not enabled
        self.seq = 0
        # the dispatch through which the MoE counters hold the device's
        # vectors (moe_stats), written beside them by counter_note
        self.moe_seq = 0
        if not self.enabled:
            return
        reg = self.registry
        # ---- registered eagerly: every metric carries its help text from
        # the first scrape, and scripts/check_metrics.py sees the literals
        self.h_ttft = reg.histogram(
            "serving_ttft_ms", "request arrival to first generated token "
            "(time-to-first-token), per completed request")
        self.h_tpot = reg.histogram(
            "serving_tpot_ms", "mean inter-token latency after the first "
            "token (time-per-output-token), per completed request")
        self.h_e2e = reg.histogram(
            "serving_e2e_ms", "request arrival to completion, per request")
        self.h_queue = reg.histogram(
            "serving_queue_ms", "request arrival to admission (first "
            "prompt chunk scheduled), per request")
        self.h_prefill = reg.histogram(
            "serving_prefill_ms", "admission to prefill complete (request "
            "decode-ready), per request")
        self.c_requests = reg.counter(
            "serving_requests_total", "requests retired, per outcome")
        self.c_tokens = reg.counter(
            "serving_tokens_total", "tokens scheduled through the serving "
            "engine, per phase (prefill / decode / spec)")
        self.c_dispatch = reg.counter(
            "serving_dispatches_total", "device dispatches issued by the "
            "serving engine, per program kind")
        self.c_mixed_slots = reg.counter(
            "serving_mixed_slots_total", "sequence slots served by mixed "
            "(SplitFuse) dispatches: prompt chunks and the decode rows "
            "that ride along")
        self.c_one_row_slots = reg.counter(
            "serving_one_row_slots_total", "slots of mixed dispatches that "
            "held exactly one row (a riding decode row, a prompt's "
            "one-token tail): the paged decode kernel attends them, the "
            "prefill kernel the others")
        self.c_prefill_items = reg.counter(
            "serving_prefill_items_total", "work items (a chunk of one "
            "slot's rows) the ragged prefill kernel attended in mixed "
            "dispatches, a layer")
        self.c_prefill_grid = reg.counter(
            "serving_prefill_grid_items_total", "work items the ragged "
            "prefill kernel's grid had room for in mixed dispatches, a "
            "layer: the static bound its live items are counted against")
        self.c_preempt = reg.counter(
            "serving_preemptions_total", "recompute-preemption victims "
            "taken, per victim state (decode_ready / mid_prefill)")
        self.g_occupancy = reg.gauge(
            "serving_batch_occupancy", "running sequences / sequence slots "
            "at the most recent dispatch")
        self.g_padding = reg.gauge(
            "serving_bucket_padding_waste", "dead fraction of the most "
            "recent mixed forward's padded token bucket "
            "((bucket - live tokens) / bucket)")
        self.c_kv_fail = reg.counter(
            "kv_alloc_failures_total", "KV block/slot requests the "
            "allocator could not satisfy, per decision site")
        self.g_kv_blocks = reg.gauge(
            "kv_pool_blocks", "paged KV pool blocks, per state "
            "(used / free)")
        self.g_kv_frag = reg.gauge(
            "kv_pool_fragmentation", "internal fragmentation of allocated "
            "KV blocks: 1 - live tokens / (allocated blocks * block size)")
        # ---- radix shared-prefix cache + SplitFuse scheduler (PR 15):
        # the control-loop families layered over the PR 5 pool signals
        self.c_prefix_lookups = reg.counter(
            "kv_prefix_lookups_total", "radix prefix-cache lookups taken "
            "at sequence admission (one per new sequence while the cache "
            "is enabled)")
        self.c_prefix_hits = reg.counter(
            "kv_prefix_hit_tokens_total", "prompt tokens whose KV was "
            "served by aliasing shared radix-cache blocks — prefill "
            "skipped for every one of them")
        self.g_shared_blocks = reg.gauge(
            "kv_shared_blocks", "KV blocks resident in the radix prefix "
            "cache, per state (cached = indexed total / shared = also "
            "held by a live sequence / evictable = reclaimable by LRU "
            "eviction right now)")
        self.c_prefill_chunks = reg.counter(
            "prefill_chunks_total", "prompt chunks the SplitFuse "
            "scheduler co-scheduled with decode tokens (one per chunk "
            "per round, bounded by prefill_chunk_tokens)")
        self.c_admissions = reg.counter(
            "serving_admissions_total", "engine admission decisions, per "
            "SLA class and decision (admitted / preempted_for)")
        self.c_sla_preempt = reg.counter(
            "serving_sla_preemptions_total", "recompute preemptions the "
            "SLA policy took to protect a higher-priority request's TTFT "
            "SLO, per victim SLA class")
        self.c_spec_outer = reg.counter(
            "spec_outer_steps_total", "speculative draft-and-verify outer "
            "steps executed, summed over sequences")
        self.c_spec_proposed = reg.counter(
            "spec_proposed_tokens_total", "draft tokens proposed to the "
            "verify step (gamma per outer step per sequence)")
        self.c_spec_accepted = reg.counter(
            "spec_draft_accepted_tokens_total", "draft-proposed tokens the "
            "verify step accepted (excludes the per-step bonus/correction "
            "token)")
        self.c_spec_emitted = reg.counter(
            "spec_emitted_tokens_total", "tokens emitted by speculative "
            "outer steps (accepted draft tokens + the bonus/correction "
            "token each step)")
        self.c_spec_ms = reg.counter(
            "spec_burst_ms_total", "wall milliseconds spent in fused "
            "speculative dispatches, including their host sync")
        self.g_spec_ratio = reg.gauge(
            "spec_accept_ratio", "cumulative draft-token acceptance: "
            "accepted / proposed")
        # ---- multi-tenant LoRA adapters (PR 20): the paged adapter pool
        # sharing the KV allocator (serving/adapters.py)
        self.c_adapter_loads = reg.counter(
            "adapter_loads_total", "LoRA adapter residency resolutions at "
            "request admission, per outcome (hit = pages already resident "
            "/ miss = first host load / reload = re-load after eviction / "
            "failed = pool could not fit the pages)")
        self.c_adapter_evict = reg.counter(
            "adapter_evictions_total", "cold LoRA adapters evicted from "
            "the shared paged pool to reclaim blocks (LRU, never a pinned "
            "adapter)")
        self.g_adapter_hit = reg.gauge(
            "adapter_hit_rate", "cumulative fraction of adapter "
            "activations served from resident pages without a host "
            "reload: hits / (hits + misses)")
        self.g_adapter_blocks = reg.gauge(
            "adapter_pool_blocks", "pool blocks holding LoRA adapter "
            "pages, per state (resident = all loaded adapters / pinned = "
            "held by in-flight requests / evictable = reclaimable by LRU "
            "eviction right now)")

        # ---- afmoe serving (Trinity): the expert layer's share and the
        # two page groups of a model with window and global layers
        self.c_moe_local = reg.counter(
            "moe_local_assignments_total", "token-to-expert assignments "
            "that went to an expert held on this chip (the rows its grouped "
            "GEMMs multiplied), summed over expert layers")
        self.c_moe_assign = reg.counter(
            "moe_assignments_total", "token-to-expert assignments the "
            "router made for live rows over all its experts, summed over "
            "expert layers")
        self.c_moe_touched = reg.counter(
            "moe_experts_touched_total", "local experts that had at least "
            "one row in a step, summed over expert layers and steps")
        self.g_kv_pages = reg.gauge(
            "kv_pages_in_use", "KV pages held by sequences, per page group "
            "(global = layers that keep a whole context / window = "
            "sliding-window layers, which keep a ring)")
        self.c_kv_released = reg.counter(
            "kv_pages_released_total", "pages a page group gave back "
            "before its sequence ended, per group (window: every token of "
            "the page lay behind the window of the oldest query to come)")
        self.c_kv_allocated = reg.counter(
            "kv_pages_allocated_total", "pages a page group handed to "
            "sequences, per group (the denominator of the released share)")
        self.c_kv_released_decode = reg.counter(
            "kv_pages_released_in_decode_total", "of the pages a page group "
            "gave back, those a fused decode burst's reservation released "
            "(window: the ring turning while a sequence only decodes), per "
            "group")
        self._kvw_seen = [0, 0, 0]  # window group totals already counted
        self.g_kv_bytes = reg.gauge(
            "kv_bytes_per_token", "device bytes the KV pool stores for one "
            "cached token over all layers (a latent pool: one padded latent "
            "row a layer; otherwise every kv head's key and value)")
        self.kv_bytes_per_token = 0     # set_kv_bytes_per_token
        self.kv_bytes_groups: Dict[str, int] = {}
        # ---- a learned selection of keys (index_topk): over the layers
        # that select, what the indexer scored, what attention then read,
        # and what it would have read without a selection
        self.c_index_pairs = reg.counter(
            "serving_index_pairs_total", "query-key pairs the indexer "
            "scored (each causal pair of a dispatch that selects), summed "
            "over the selecting layers")
        self.c_sel_pairs = reg.counter(
            "serving_selected_pairs_total", "query-key pairs attention "
            "kept on the selecting layers: min(keys seen, index_topk) a row")
        self.c_global_pairs = reg.counter(
            "serving_global_pairs_total", "causal query-key pairs on the "
            "selecting layers: what dense attention would read")
        self.c_sel_masked = reg.counter(
            "serving_selected_masked_steps_total", "mixed dispatches whose "
            "prompt chunks read their selected keys through the masked "
            "prefill kernel and not by the row gather "
            "(ops.sparse_index.masked_prefill of the step's reach)")

        # ---- a selection by blocks (block_topk): made by
        # set_block_selection, so another model's registry has no such name
        self.block_layers = 0
        self.c_blk_rows = self.c_blk_kept = None

        # ---- state layers (layer_types): the rows they mix by path, and
        # the resident that does not grow, one state slot a sequence; under
        # the names of the kind the model has ("ssm": Mamba-2 scan layers,
        # a float32 state and a conv tail; "conv": gated short
        # convolutions, a conv tail alone), which set_scan_state picks
        self._state_metrics = {
            "ssm": (
                reg.counter(
                    "serving_ssm_rows_total", "rows through the scan "
                    "layers, rows times scan layers, per path (chunk = a "
                    "prompt chunk's rows through the chunked scan / step = "
                    "a one-row slot's through the recurrence)"),
                reg.gauge(
                    "ssm_state_slots_in_use", "scan-state slots held by "
                    "tracked sequences (one a sequence, allocated with it "
                    "and freed with it) at the most recent dispatch"),
                reg.gauge(
                    "ssm_state_bytes_per_slot", "device bytes of one "
                    "sequence's state slot over all scan layers: the "
                    "float32 recurrent state and the conv's last rows")),
            "conv": (
                reg.counter(
                    "serving_conv_rows_total", "rows through the "
                    "short-conv layers, rows times conv layers, per path "
                    "(chunk = a prompt chunk's rows, convolved from its "
                    "slot's tail / step = a one-row slot's, which shifts "
                    "its tail)"),
                reg.gauge(
                    "conv_state_slots_in_use", "conv-tail slots held by "
                    "tracked sequences (one a sequence, allocated with it "
                    "and freed with it) at the most recent dispatch"),
                reg.gauge(
                    "conv_state_bytes_per_slot", "device bytes of one "
                    "sequence's state slot over all short-conv layers: "
                    "the conv's last rows, in the compute dtype"))}
        self.c_ssm_rows, self.g_ssm_slots, self.g_ssm_bytes = \
            self._state_metrics["ssm"]
        # ---- a multi-stream residual (hc_mult > 1): made by set_hc, so a
        # one-stream model's registry has neither name
        self.hc_sublayers = 0
        self.hc_bytes_per_row = 0
        self.c_hc_rows = self.g_hc_bytes = None

    # ------------------------------------------------------------- clocks

    @staticmethod
    def now() -> float:
        """Lifecycle clock (seconds).  One definition so engine timestamps
        and histogram math never mix clock bases."""
        return time.perf_counter()

    # -------------------------------------------------------------- spans

    def span(self, name: str, **args):
        """A scheduler phase or dispatch: the ``ds.<name>`` profiler
        annotation always, the tracer's buffered event when it is on."""
        return self.tracer.span(name, **args)

    def dispatch_span(self, kind: str, program, **args):
        """One step-program dispatch: the next ``seq``, the kind counted
        and the ``ds.<kind>_dispatch`` span opened in one place, so that a
        span and its count cannot drift apart.  The span says which
        dispatch of this engine it is (``seq``) and which program it means
        to launch (``program``, the jitted callable's name: what the
        profiler's ``XLA Modules`` line prints after ``jit_``), so a trace
        reader can hold the device run it joins by the runtime's ``run_id``
        to both."""
        self.seq += 1
        self.dispatch(kind)
        return self.tracer.span(f"{kind}_dispatch", seq=self.seq,
                                program=program.__name__, **args)

    # ---------------------------------------------------- request lifecycle

    def new_track(self, label: str) -> int:
        """Allocate a trace track (tid) for one request; tid 0 stays the
        engine dispatch track.  Track NAMES are bounded by the event-buffer
        size: a long-lived engine serves unboundedly many requests, and an
        unbounded thread_names dict would leak ~100B per request forever
        (the span deque itself is bounded) — requests past the bound still
        get a tid, just no name metadata (the bound now lives inside
        ``SpanTracer.set_thread_name``)."""
        self._track_count += 1
        tid = self._track_count
        if self.tracer.enabled:
            self.tracer.set_thread_name(tid, label)
        return tid

    def finish_request(self, *, uid, track: int, t_arrival: float,
                       t_admit: Optional[float],
                       t_prefill_end: Optional[float],
                       t_first: Optional[float], t_last: Optional[float],
                       n_prompt: int, n_generated: int,
                       preempts: int = 0, outcome: str = "completed",
                       trace=None) -> None:
        """Record one retired request: latency histograms + the three
        lifecycle spans on the request's own track.  Timestamps are
        ``now()`` seconds; missing stages (a zero-token completion) are
        skipped rather than guessed."""
        if not self.enabled:
            return
        self.c_requests.inc(1, outcome=outcome, **self.labels)
        t_done = t_last if t_last is not None else self.now()
        rec = {"uid": uid, "outcome": outcome,
               "prompt_tokens": int(n_prompt),
               "generated_tokens": int(n_generated),
               "preempts": int(preempts),
               "e2e_ms": (t_done - t_arrival) * 1e3,
               "ttft_ms": None, "tpot_ms": None}
        self.h_e2e.observe(rec["e2e_ms"], **self.labels)
        if t_admit is not None:
            self.h_queue.observe((t_admit - t_arrival) * 1e3, **self.labels)
            if t_prefill_end is not None:
                self.h_prefill.observe((t_prefill_end - t_admit) * 1e3,
                                       **self.labels)
        if t_first is not None:
            rec["ttft_ms"] = (t_first - t_arrival) * 1e3
            self.h_ttft.observe(rec["ttft_ms"], **self.labels)
            if t_last is not None and n_generated > 1:
                rec["tpot_ms"] = (t_last - t_first) * 1e3 / (n_generated - 1)
                self.h_tpot.observe(rec["tpot_ms"], **self.labels)
        if len(self.request_log) < self.request_log_cap:
            self.request_log.append(rec)
        if self.tracer.enabled:
            args = {"uid": uid, "prompt_tokens": int(n_prompt),
                    "generated_tokens": int(n_generated),
                    "preempts": int(preempts), "outcome": outcome}
            if trace is not None:
                # distributed-trace coordinates: critical_path.py matches
                # these engine spans back to fleet requests by (trace,
                # phase) and picks the final attempt by timestamp
                args.update(trace.args())
            spans = [("queue_wait", t_arrival, t_admit),
                     ("prefill", t_admit, t_prefill_end),
                     ("decode", t_prefill_end, t_last)]
            first_ts = None
            for name, a, b in spans:
                if a is None or b is None or b < a:
                    continue
                ts = self.tracer.us_of(a)
                if first_ts is None:
                    first_ts = (ts, (b - a) * 1e6)
                self.tracer.record(name, ts, (b - a) * 1e6,
                                   tid=track, cat="request", **args)
            if (trace is not None and trace.flow_id is not None
                    and first_ts is not None):
                # flow step binding to this replica's first lifecycle
                # slice: the router's `s` event + this `t` + the fleet's
                # `f` stitch the request into one cross-replica tree
                self.tracer.flow("t", trace.flow_id,
                                 first_ts[0] + first_ts[1] / 2, tid=track)

    # ----------------------------------------------------------- counters

    def dispatch(self, kind: str) -> None:
        if self.enabled:
            self.c_dispatch.inc(1, kind=kind, **self.labels)

    def tokens(self, phase: str, n: int) -> None:
        if self.enabled and n:
            self.c_tokens.inc(n, phase=phase, **self.labels)

    def mixed_slots(self, rows, items: int, grid_items: int) -> None:
        """One mixed dispatch's slots, by the rows each holds, and the
        prefill kernel's work items: live, and what its grid is bound to."""
        if self.enabled:
            self.c_mixed_slots.inc(len(rows), **self.labels)
            self.c_one_row_slots.inc(sum(n == 1 for n in rows),
                                     **self.labels)
            self.c_prefill_items.inc(items, **self.labels)
            self.c_prefill_grid.inc(grid_items, **self.labels)

    def moe_stats(self, vec, seq: int) -> None:
        """One dispatch's MoE counter vector (model.py ``_ffn``): [local
        assignments, assignments of live rows, local experts touched];
        ``seq`` is that dispatch's, and the totals are then the device's
        through it."""
        self.moe_seq = seq
        if self.enabled:
            self.c_moe_local.inc(int(vec[0]), **self.labels)
            self.c_moe_assign.inc(int(vec[1]), **self.labels)
            self.c_moe_touched.inc(int(vec[2]), **self.labels)

    def set_kv_bytes_per_token(self, n: int, **groups: int) -> None:
        """The engine's pool geometry, once at start-up: the gauge beside
        ``kv_pages_in_use`` and an argument of every dispatch span.
        ``groups``: its parts where the pool has more than one
        (``kv_bytes_per_token_global`` / ``_window``,
        ``index_bytes_per_token``): a gauge series and a span argument
        each."""
        self.kv_bytes_per_token = int(n)
        self.kv_bytes_groups = {k: int(v) for k, v in groups.items()}
        if self.enabled:
            self.g_kv_bytes.set(int(n), **self.labels)
            for k, v in self.kv_bytes_groups.items():
                self.g_kv_bytes.set(v, part=k, **self.labels)

    def set_block_selection(self, layers: int) -> None:
        """A model whose attention layers select their keys by blocks
        (``GPTConfig.block_topk``), once at start-up: how many layers
        select."""
        self.block_layers = int(layers)
        if self.enabled:
            self.c_blk_rows = self.registry.counter(
                "serving_block_rows_total", "rows through the layers that "
                "select by blocks, rows times selecting layers, per path "
                "(dense = context within block_dense_len, every key read / "
                "sparse = past it, block_topk blocks kept a KV head)")
            self.c_blk_kept = self.registry.counter(
                "serving_block_kept_blocks_total", "blocks the sparse rows "
                "kept, a KV head (block_topk a row), summed over the "
                "selecting layers")

    def block_rows(self, dense: int, sparse: int, kept_blocks: int) -> None:
        """One dispatch's rows through the selecting layers by path, and
        the blocks its sparse rows kept."""
        if self.enabled and self.block_layers:
            self.c_blk_rows.inc(dense, path="dense", **self.labels)
            self.c_blk_rows.inc(sparse, path="sparse", **self.labels)
            self.c_blk_kept.inc(kept_blocks, **self.labels)

    def set_scan_state(self, layers: int, bytes_per_slot: int,
                       kind: str = "ssm") -> None:
        """A model with state layers, once at start-up: how many it has,
        the bytes of one sequence's state slot over all of them (whatever
        parts the pool has: a conv tail, and a recurrent state where layers
        scan) and the kind, "ssm" or "conv", whose names the rows, the slots
        and the bytes are reported under."""
        self.scan_layers = int(layers)
        self.ssm_bytes_per_slot = int(bytes_per_slot)
        self.state_kind = kind
        if self.enabled:
            (self.c_ssm_rows, self.g_ssm_slots,
             self.g_ssm_bytes) = self._state_metrics[kind]
            self.g_ssm_bytes.set(int(bytes_per_slot), **self.labels)

    def ssm_rows(self, rows, steps: int = 1) -> None:
        """One dispatch's rows through the state layers, by path: a slot
        with one row takes the one-row route (``step``), one with more the
        chunked one (``chunk``); ``rows``: each scheduled sequence's,
        ``steps``: of a fused burst.  Rows times state layers."""
        if self.enabled and self.scan_layers:
            n = self.scan_layers * steps
            self.c_ssm_rows.inc(n * sum(r for r in rows if r > 1),
                                path="chunk", **self.labels)
            self.c_ssm_rows.inc(n * sum(r == 1 for r in rows), path="step",
                                **self.labels)

    def set_hc(self, sublayers: int, bytes_per_row: int) -> None:
        """A model whose residual is several streams a token
        (``GPTConfig.hc_mult`` > 1), once at start-up: how many sublayers
        mix them (two a layer) and the bytes of one row's streams
        (``hc_mult * hidden * itemsize``), which each of them reads and
        writes."""
        self.hc_sublayers = int(sublayers)
        self.hc_bytes_per_row = int(bytes_per_row)
        if self.enabled:
            self.c_hc_rows = self.registry.counter(
                "serving_hc_rows_total", "rows through the residual "
                "streams' mix, rows times mixing sublayers (two a layer), "
                "per phase (prefill = a prompt chunk's rows / decode = a "
                "one-row slot's)")
            self.g_hc_bytes = self.registry.gauge(
                "hc_residual_bytes_per_row", "device bytes of one row's "
                "residual streams (hc_mult x hidden x itemsize): what a "
                "mixing sublayer reads and writes back")
            self.g_hc_bytes.set(int(bytes_per_row), **self.labels)

    def hc_rows(self, rows, steps: int = 1) -> Dict[str, int]:
        """One dispatch's rows through the mix, rows times mixing
        sublayers (``rows``: each scheduled sequence's, ``steps``: of a
        fused burst), counted by phase and returned as the dispatch span's
        arguments ``hc_rows`` (this dispatch's own, not a running total)
        and ``hc_residual_bytes_per_row``; {} for a one-stream model."""
        if not (self.enabled and self.hc_sublayers):
            return {}
        n = self.hc_sublayers * steps
        prefill = n * int(sum(r for r in rows if r > 1))
        decode = n * int(sum(r == 1 for r in rows))
        self.c_hc_rows.inc(prefill, phase="prefill", **self.labels)
        self.c_hc_rows.inc(decode, phase="decode", **self.labels)
        return {"hc_rows": prefill + decode,
                "hc_residual_bytes_per_row": self.hc_bytes_per_row}

    def index_pairs(self, scored: int, kept: int, causal: int,
                    masked_step: bool = False) -> None:
        """One dispatch's pairs on the selecting layers, and whether its
        prompt chunks took the masked prefill kernel."""
        if self.enabled:
            self.c_index_pairs.inc(scored, **self.labels)
            self.c_sel_pairs.inc(kept, **self.labels)
            self.c_global_pairs.inc(causal, **self.labels)
            self.c_sel_masked.inc(int(masked_step), **self.labels)

    def counter_note(self, state) -> Dict[str, int]:
        """Running totals for a dispatch span's args, so that a trace holds
        them (a reader takes the difference between two dispatches): the
        slots mixed dispatches served and those of them with one row, the
        prefill kernel's live work items and its grid's, the MoE counters
        as far as the device has reported (through dispatch ``moe_seq``)
        and the window page group's; the last two only for a model that
        has them."""
        note: Dict[str, int] = {}
        if not self.enabled:
            return note
        note.update(
            kv_bytes_per_token=self.kv_bytes_per_token,
            mixed_seqs=int(self.c_mixed_slots.value(**self.labels)),
            one_row_seqs=int(self.c_one_row_slots.value(**self.labels)),
            prefill_items=int(self.c_prefill_items.value(**self.labels)),
            prefill_grid_items=int(self.c_prefill_grid.value(**self.labels)))
        note.update(self.kv_bytes_groups)
        pairs = self.c_global_pairs.value(**self.labels)
        if pairs:
            note.update(
                index_pairs=int(self.c_index_pairs.value(**self.labels)),
                sel_pairs=int(self.c_sel_pairs.value(**self.labels)),
                sel_masked_steps=int(
                    self.c_sel_masked.value(**self.labels)),
                global_pairs=int(pairs))
        if self.block_layers:
            note.update(
                blk_dense_rows=int(self.c_blk_rows.value(
                    path="dense", **self.labels)),
                blk_sparse_rows=int(self.c_blk_rows.value(
                    path="sparse", **self.labels)),
                blk_kept_blocks=int(self.c_blk_kept.value(**self.labels)))
        if self.scan_layers:
            slots = state.scan_slots_in_use
            self.g_ssm_slots.set(slots, **self.labels)
            kind = self.state_kind
            note.update({
                f"{kind}_chunk_rows": int(self.c_ssm_rows.value(
                    path="chunk", **self.labels)),
                f"{kind}_step_rows": int(self.c_ssm_rows.value(
                    path="step", **self.labels)),
                f"{kind}_slots": slots,
                f"{kind}_state_bytes_per_slot": self.ssm_bytes_per_slot})
        total = self.c_moe_assign.value(**self.labels)
        if total:
            note.update(
                moe_assign=int(total),
                moe_local=int(self.c_moe_local.value(**self.labels)),
                moe_touched=int(self.c_moe_touched.value(**self.labels)),
                moe_seq=self.moe_seq)
        if getattr(state, "window", None):
            self._fold_window_pages(state)
            note.update(
                kvw_allocated=state.w_allocated_total,
                kvw_released=state.w_released_total,
                kvw_released_decode=state.w_released_decode_total,
                kv_pages_window=(state.wallocator.num_blocks
                                 - state.wallocator.free_blocks),
                kv_pages_global=(state.allocator.num_blocks
                                 - state.allocator.free_blocks))
        return note

    def _fold_window_pages(self, state) -> None:
        """The window group's running totals into its counters: at every
        pool sample and every dispatch (a burst's reservation releases pages
        between two samples)."""
        now = [state.w_allocated_total, state.w_released_total,
               state.w_released_decode_total]
        for counter, n, seen in zip(
                (self.c_kv_allocated, self.c_kv_released,
                 self.c_kv_released_decode), now, self._kvw_seen):
            counter.inc(n - seen, group="window", **self.labels)
        self._kvw_seen = now

    def preemption(self, kind: str) -> None:
        if self.enabled:
            self.c_preempt.inc(1, kind=kind, **self.labels)

    def sla_preemption(self, sla: str) -> None:
        if self.enabled:
            self.c_sla_preempt.inc(1, sla=sla, **self.labels)

    def admission(self, sla: str, decision: str = "admitted") -> None:
        if self.enabled:
            self.c_admissions.inc(1, sla=sla, decision=decision,
                                  **self.labels)

    def prefix_lookup(self, hit_tokens: int) -> None:
        """One radix-cache admission lookup; ``hit_tokens`` is the matched
        prefix length actually aliased (0 on a miss)."""
        if self.enabled:
            self.c_prefix_lookups.inc(1, **self.labels)
            if hit_tokens:
                self.c_prefix_hits.inc(hit_tokens, **self.labels)

    def prefill_chunk(self) -> None:
        if self.enabled:
            self.c_prefill_chunks.inc(1, **self.labels)

    def occupancy(self, running: int, slots: int) -> None:
        if self.enabled and slots:
            self.g_occupancy.set(running / slots, **self.labels)

    def padding_waste(self, live_tokens: int, bucket: int) -> None:
        if self.enabled and bucket:
            self.g_padding.set((bucket - live_tokens) / bucket, **self.labels)

    # ------------------------------------------------------------ KV pool

    def alloc_failure(self, site: str, n: int = 1) -> None:
        if self.enabled:
            self.c_kv_fail.inc(n, site=site, **self.labels)

    def kv_sample(self, state) -> None:
        """Gauge the paged pool off a DSStateManager: used/free blocks and
        internal fragmentation.  O(tracked sequences) — called once per
        scheduler round, not per token."""
        if not self.enabled:
            return
        free = state.allocator.free_blocks
        total = state.allocator.num_blocks
        used = total - free
        self.g_kv_blocks.set(used, state="used", **self.labels)
        self.g_kv_blocks.set(free, state="free", **self.labels)
        if getattr(state, "window", None):
            wa = state.wallocator
            self.g_kv_pages.set(used, group="global", **self.labels)
            self.g_kv_pages.set(wa.num_blocks - wa.free_blocks,
                                group="window", **self.labels)
            self._fold_window_pages(state)
        alloc_tokens = 0
        live_tokens = 0
        for seq in state.tracked.values():
            alloc_tokens += len(seq.blocks) * state.block_size
            live_tokens += seq.seen_tokens
        self.g_kv_frag.set(
            1.0 - live_tokens / alloc_tokens if alloc_tokens else 0.0,
            **self.labels)
        radix = getattr(state, "radix", None)
        if radix is not None:
            st = radix.stats()
            self.g_shared_blocks.set(st["nodes"], state="cached",
                                     **self.labels)
            self.g_shared_blocks.set(st["shared"], state="shared",
                                     **self.labels)
            self.g_shared_blocks.set(st["evictable"], state="evictable",
                                     **self.labels)
        pool = getattr(state, "adapters", None)
        if pool is not None:
            st = pool.stats()
            self.g_adapter_blocks.set(st["resident_blocks"],
                                      state="resident", **self.labels)
            self.g_adapter_blocks.set(st["pinned_blocks"], state="pinned",
                                      **self.labels)
            self.g_adapter_blocks.set(st["evictable_blocks"],
                                      state="evictable", **self.labels)

    # ------------------------------------------------- multi-tenant adapters

    def adapter_load(self, outcome: str, hit_rate: float) -> None:
        """One adapter residency resolution (AdapterPool.ensure); the pool
        passes its cumulative hit rate so the gauge tracks the counter
        without a registry read-back."""
        if self.enabled:
            self.c_adapter_loads.inc(1, outcome=outcome, **self.labels)
            self.g_adapter_hit.set(hit_rate, **self.labels)

    def adapter_eviction(self, n: int = 1) -> None:
        if self.enabled:
            self.c_adapter_evict.inc(n, **self.labels)

    # -------------------------------------------------------- speculative

    def spec_burst(self, *, outer: int, n_seqs: int, gamma: int,
                   emitted: int, dur_ms: float) -> None:
        """Account one fused speculative dispatch: ``emitted`` is the total
        token count the burst produced (counts.sum over the served slots);
        every outer step also emits exactly one non-draft bonus/correction
        token, so draft-accepted = emitted - outer*n_seqs."""
        if not self.enabled:
            return
        steps = outer * n_seqs
        self.c_spec_outer.inc(steps, **self.labels)
        self.c_spec_proposed.inc(steps * gamma, **self.labels)
        self.c_spec_accepted.inc(max(0, emitted - steps), **self.labels)
        self.c_spec_emitted.inc(emitted, **self.labels)
        self.c_spec_ms.inc(dur_ms, **self.labels)
        proposed = self.c_spec_proposed.value(**self.labels)
        if proposed:
            self.g_spec_ratio.set(
                self.c_spec_accepted.value(**self.labels) / proposed,
                **self.labels)

    def spec_summary(self) -> Dict[str, float]:
        """The bench/test-facing read of the speculative counters (replaces
        the old ``eng.spec_stats`` dict)."""
        if not self.enabled:
            return {}
        L = self.labels
        proposed = self.c_spec_proposed.value(**L)
        outer = self.c_spec_outer.value(**L)
        return {
            "outer_steps": outer,
            "proposed": proposed,
            "accepted": self.c_spec_accepted.value(**L),
            "emitted": self.c_spec_emitted.value(**L),
            "accept_ratio": (self.c_spec_accepted.value(**L) / proposed
                             if proposed else 0.0),
            "emitted_per_outer": (self.c_spec_emitted.value(**L) / outer
                                  if outer else 0.0),
            "burst_ms": self.c_spec_ms.value(**L),
            # fused draft+verify dispatches: the cross-request batching
            # claim is "dispatches per emitted token strictly lower than
            # per-request spec" — this is the numerator the tests pin
            "spec_dispatches": self.c_dispatch.value(kind="spec", **L),
        }

    # -------------------------------------------------------------- reads

    def value(self, name: str, **labels) -> float:
        """Read one series; an instance's own replica label (fleet mode) is
        merged in so callers address "my" series by the same names a
        single-engine setup uses (pass ``replica=...`` to override)."""
        m = self.registry._metrics.get(name)
        return m.value(**{**self.labels, **labels}) if m is not None else 0.0

    def quantile(self, name: str, q: float, **labels) -> float:
        m = self.registry._metrics.get(name)
        if m is None or m.kind != "histogram":
            return float("nan")
        return m.quantile(q, **{**self.labels, **labels})

    # ------------------------------------------------------------- export

    def export(self, out_dir: str, extra: Optional[dict] = None) -> dict:
        """Write snapshot.json + metrics.prom + trace.json under
        ``out_dir`` and return the snapshot dict.  The trace is the
        combined dispatch (tid 0) + per-request track view Perfetto
        loads directly."""
        if not self.enabled:
            return {}
        os.makedirs(out_dir, exist_ok=True)
        snap = self.exporter.snapshot(extra=extra)
        self.exporter.write_json(os.path.join(out_dir, "snapshot.json"),
                                 snap)
        self.exporter.write_prometheus(
            os.path.join(out_dir, "metrics.prom"), snap)
        if self.tracer.enabled and self.tracer.events:
            self.emitter.write(os.path.join(out_dir, "trace.json"),
                               self.tracer)
        return snap
