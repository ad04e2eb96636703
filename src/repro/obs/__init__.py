"""Runtime telemetry: jit-safe metrics, invariant counters, trace spans.

Scopes and host spans are always on; metrics are opt-in.

* Scopes (``obs.span``, ``jax.named_scope``) name the ops of a phase in
  their ``op_name`` metadata, and host spans (``obs.host_span``,
  ``jax.profiler.TraceAnnotation``) mark a phase of host code on the
  profiler's clock, so every profile of every run shows them.  A scope is
  metadata only: with metadata stripped, the compiled program is the same
  with or without it (asserted in ``tests/test_obs.py``).  A host span
  costs about a microsecond when no profiler session records.
* Metrics (``counter`` / ``gauge`` / ``histogram``) are on after
  ``obs.enable(metrics_dir=...)`` (JSONL under that directory) or
  ``obs.enable(sink=...)`` / ``obs.capture()`` (in-memory, tests).  While
  disabled — the default — every record point is a trace-time no-op:
  instrumented functions compile to identical HLO, modulo debug metadata,
  so the hot paths carry their probes for free.  Enabled, a record made
  while tracing rides a ``jax.debug.callback``; one made in host code
  goes straight to the sink.

JSONL schema (one object per line)::

    {"ts": <unix float>, "metric": "<dotted.name>",
     "kind": "counter" | "gauge" | "histogram" | "event",
     "step": <int, when set_step() was called>,
     "value": <scalar or list>            # counter/gauge
     "count"/"min"/"p50"/"p90"/"max"/"sum": ...  # histogram summary
     "labels": {<static + traced labels>}}

Under ``shard_map`` each device emits its own record (instrumented sites
attach ``device=lax.axis_index(axis)`` as a label); under ``vmap`` each
lane does.

Metrics catalog — every record point woven through the hot paths:

== Proposition 1 (co-rank search cost) ==
``corank.iterations``        histogram, per search: actual while-loop
                             iterations of Algorithm 1; labels
                             ``bound = ceil(log2 min(m, n)) + 1`` (the
                             paper's bound; value <= bound always),
                             ``m``, ``n``.
``kway.corank_rounds``       gauge: lock-step bisection rounds of the
                             k-way cut, one per bit of the keys' range
                             (32 for int32 and floats, 8 for int8);
                             labels ``bound``, ``k``, ``w``, ``search``
                             (``"value"``: the key bisection).
``splitters.kway_rounds``    gauge: collective rounds of the
                             distributed k-way splitter; labels
                             ``bound``, ``w``, ``device``.
``splitters.pairwise_rounds``gauge: rounds of distributed Algorithm 1;
                             labels ``bound``, ``m``, ``n``.
``splitters.segment_cut_scalars`` counter: int32 scalars gathered by
                             the one-round value-keyed segment cuts
                             (``p * (E+1)``); labels ``n_segments``.

== Proposition 2 (perfect balance) ==
``kway.partition_sizes``     gauge, ``(p,)``: per-PE output block sizes
                             of ``merge_kway`` (differ by <= 1).
``kway.partition_imbalance`` gauge: max - min of the above (0 or 1).
``exchange.block_elements``  gauge: this device's received real
                             elements, ``== N/p`` on the sort path;
                             labels ``device``.

== Exchange traffic ==
``exchange.peer_bytes``      gauge, ``(p,)``: real payload bytes
                             received per source peer (lengths sideband
                             x itemsize); labels ``device``,
                             ``capacity``, ``itemsize``.
``exchange.send_lengths``    gauge, ``(p,)``: elements sent per peer
                             (pre-truncation clip); labels ``device``.
``exchange.padding_slots``   gauge: sentinel-padded slots shipped
                             (``p*capacity - sum(lengths)``) — the
                             static-shape overhead; labels ``device``.
``exchange.length_skew``     gauge: max - min of per-peer segment
                             lengths (raggedness); labels ``device``.

== MoE routing ==
``moe.planned_per_source``   gauge, ``(p,)``: assignments each source
                             planned to send me (from the cut matrix).
``moe.recv_per_source``      gauge, ``(p,)``: assignments that arrived
                             (sideband).
``moe.overflow``             counter: planned - received, summed — the
                             exact per-step drop count (0 at default
                             capacity); labels ``device``.
``moe.group_sizes``          gauge, ``(e_per,)``: rows per owned expert
                             feeding the grouped GEMMs.
``moe.routing_skew``         gauge: max(group_sizes) / mean — 1.0 is
                             perfectly uniform routing.

== External (out-of-core) sort ==
``external.runs_spilled``    counter: sorted runs written to host by
                             the spill phase.
``external.bytes_spilled``   counter: bytes those runs occupy on disk
                             (keys + payload).
``external.windows_merged``  counter: output windows made durable by
                             the streaming k-way merge.
``external.merge_passes``    gauge: fanout-capped passes a sort took
                             (``ceil(log_fanout(n_runs))``).
``external.device_resident_bytes`` gauge: bytes on device right now —
                             one chunk during ``phase="chunk_sort"``,
                             two staged ``(k, window)`` buffers + one
                             output window during ``phase="merge"``
                             (the O(fanout * window) bound
                             ``tests/test_external.py`` asserts).
``external.resident_boundary_elems`` gauge: input elements the host
                             co-rank planner materialises per probe —
                             exactly ``k`` (labels ``bound = k``), the
                             partition-without-merging property.
``external.plan_probes``     counter: boundary probes per cut search
                             (``<= k * (ceil(log2 w) + 1)``).
``external.copy_compute_overlap`` gauge in [0, 1]: fraction of host
                             staging time hidden behind an in-flight
                             device merge (double-buffering quality);
                             labels ``k``.

== Serving (continuous batching) ==
``serve.admitted``           counter: requests moved from the queue
                             into KV-pool slots this step.
``serve.completed``          counter: requests retired this step.
``serve.queue_depth``        gauge: requests waiting for a slot.
``serve.active_slots``       gauge: occupied slots after admission;
                             labels ``capacity`` — the harness asserts
                             value <= capacity on every step.
``serve.slots_recycled``     counter: slot free() calls (recycling is a
                             length reset, never a KV zeroing pass).
``serve.topk_merge_rounds``  gauge: merge_kway cuts per batched top-k
                             call — a function of vocab/fanout geometry
                             only, NEVER batch size (the one-merge-
                             per-step claim); labels ``blocks``,
                             ``fanout``.
``serve.topk_candidates``    counter: candidate keys entering the final
                             tournament cut (``batch x runs x k``);
                             labels ``batch``, ``k``.

== Dispatch / compile ==
``kernels.backend_selected`` event, once per (op, backend): which
                             backend ``repro.kernels.ops`` dispatch
                             chose and why (env override vs auto).
``kernels.dispatch_calls``   counter per traced call; labels ``op``,
                             ``backend``.
``hlo.collectives``          event: HLO-predicted collective bytes of a
                             jitted entrypoint (``attach_hlo_report``).
``hlo.report_failed``        event: attach_hlo_report swallowed an
                             exception; labels ``entry``,
                             ``error_type``, ``error``.
``obs.profile_started`` / ``obs.profile_stopped`` events: profiler
                             trace-dump window (``--profile-steps``).

Scopes (``obs.span``; stable ``repro.<name>``, read from a device trace's
``tf_op`` stat by ``bench/scopes.py``):

== Subsystem boundaries ==
``repro.stable_merge`` / ``repro.stable_merge_kway`` /
``repro.merge_window`` / ``repro.stable_sort`` (kernel dispatch),
``repro.merge_kway``, ``repro.sharded_sort``, ``repro.local_sort``,
``repro.sharded_merge_kway.<strategy>``, ``repro.splitters``,
``repro.local_merge``, ``repro.exchange_block``,
``repro.dropless_moe_ffn``, ``repro.dropless_dispatch``,
``repro.moe_grouped_gemm``, ``repro.dropless_combine``.

== Phases of the hot paths ==
``repro.corank``             Pallas merges (``merge_pallas``,
                             ``merge_kway_pallas``): phase 1, the
                             co-rank of every tile boundary.
``repro.slab_relayout``      ... padding to tiles, widening, the slab
                             layout and payload concatenation, and the
                             reshape, slice and narrowing of the result.
``repro.tile_merge``         ... the Pallas tile-merge calls.
``repro.topk_block_sort``    ``merge_topk_batch``: negate, pad, the
                             in-block stable sort, per-block top-k.
``repro.topk_tournament``    ... the candidate-merge rounds.
``repro.topk_draw``          ``sample_topk_batched`` /
                             ``sample_topp_batched``: softmax, the
                             nucleus cut, the categorical draw.
``repro.decoder_layers``     ragged decode step: the layer scan; what
                             lies under it but under neither of the next
                             two is the scan's own per-layer slicing and
                             stacking of the KV cache.
``repro.attention``          ... in the scan body: ln1, QKV, the KV
                             write, attention, output projection and
                             residual.
``repro.ffn``                ... in the scan body: the FFN block.

Host spans (``obs.host_span``): ``repro.external_sort``,
``serve.prefill`` (lock-step launcher), and ``DecodeEngine.step``'s
``serve.step`` around ``serve.admit`` (admission, slot allocation),
``serve.build_inputs`` (input arrays and their transfer),
``serve.dispatch`` (decode step, cache swap, keys, sampler),
``serve.fetch_tokens`` (the blocking wait for sampled tokens) and
``serve.retire`` (per-slot bookkeeping, completion, slot free).
Launcher loops also mark steps with ``step_span`` while obs is enabled.
"""

from repro.obs.registry import (
    capture,
    counter,
    disable,
    enable,
    enabled,
    flush,
    gauge,
    histogram,
    log_event,
    record,
    set_step,
    totals,
)
from repro.obs.sink import JsonlSink, ListSink, Sink
from repro.obs.trace import (
    attach_hlo_report,
    host_span,
    span,
    start_profile,
    step_span,
    stop_profile,
)

__all__ = [
    "enable",
    "disable",
    "enabled",
    "capture",
    "record",
    "counter",
    "gauge",
    "histogram",
    "log_event",
    "set_step",
    "flush",
    "totals",
    "Sink",
    "ListSink",
    "JsonlSink",
    "span",
    "host_span",
    "step_span",
    "start_profile",
    "stop_profile",
    "attach_hlo_report",
]
