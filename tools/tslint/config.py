"""tslint configuration: per-rule options + deep merge.

The defaults are tuned to THIS repo (the hot-function list names the
train/decode/input loops whose per-step host syncs erase kernel wins —
see ANALYSIS.md for why each entry is hot).  Tests and other checkouts
override by passing a partial config dict to ``engine.analyze`` — it is
deep-merged over these defaults, so overriding one rule key keeps the
rest.
"""

from __future__ import annotations

import copy
from typing import Any, Dict, Optional

#: Default scan target for the CLI when no paths are given.
DEFAULT_PATHS = ("textsummarization_on_flink_tpu",)

#: Default baseline location (relative to the scan root) the CLI picks
#: up when --baseline is not given and the file exists.
DEFAULT_BASELINE = "tools/tslint/baseline.json"

DEFAULT: Dict[str, Any] = {
    "exclude_dirs": {"__pycache__", ".git", ".jax_cache", "exp"},
    "rules": {
        "TS001": {
            "enabled": True,
            # dotted-call roots that are side effects at trace time: the
            # call runs ONCE while jit traces and never again on device
            "impure_roots": ["time", "os", "random", "logging", "log",
                             "obs", "np.random"],
            # sanctioned escape hatches (run on device / at runtime)
            "allowed_prefixes": ["jax.debug"],
        },
        "TS002": {
            "enabled": True,
            # qualname regexes of per-step/per-token loops where one
            # stray sync serializes dispatch (matched with re.search)
            "hot_functions": [
                r"^Trainer\._train_steps$",
                r"^Evaluator\.run$",
                r"^DevicePrefetcher\.next_batch$",
                r"^Batcher\.next_batch$",
                r"^BeamSearchDecoder\.decode$",
                # the continuous-serving dispatch path (ISSUE 6): one
                # stray per-slot sync here serializes every resident
                # request's chunk cadence
                r"^ContinuousBatcher\.(tick|_refill|_harvest|_evict_expired)$",
                r"^ServingServer\._run_continuous$",
                r"^SlotDecodeEngine\.(pack|step|unpack|unpack_many|prefill)$",
                # prefill/decode disaggregation (ISSUE 11): the prefill
                # stage runs once per admission on the dispatch thread,
                # and the blocked/masked attention closures trace into
                # every decode chunk — a host sync (or trace-time side
                # effect) in any of them stalls resident decodes
                r"^ContinuousBatcher\._prefill_stage$",
                r"^_attend_shared_blocked",
                r"^cross_attend_layer",
                # the telemetry plane's own per-tick/per-step code
                # (ISSUE 9): frame recording and heartbeats run inside
                # every hot loop above — a host sync smuggled into THEM
                # would serialize the loops they observe
                r"^ContinuousBatcher\._record_frame$",
                r"^FlightRecorder\.record$",
                r"^HeartbeatBoard\.beat$",
                r"^ServeFuture\._finish$",
                # the decode byte diet's restructured search (ISSUE 7):
                # the backpointer body and the finalize backtrack are the
                # per-step/per-retire hot code — one stray host sync (or
                # trace-time side effect) here serializes every dispatch
                r"^_make_beam_body",  # covers the <locals>.body closure
                r"^_finalize_beam",  # covers the <locals>.back backtrack
                # the unified sharded step builder (ISSUE 8): its traced
                # closures (train_step body, the wire-dtype grad fn) run
                # every optimizer step on every chip — a stray host sync
                # or trace-time side effect here poisons the whole mesh
                r"^make_sharded_train_step",
                r"^_make_wire_grad_fn",
                # the speculative fast path (ISSUE 10): the draft-verify
                # cycle body and the parallel verify run once per
                # emitted-token group, and the AAN decode step once per
                # draft token — a host sync in any of them serializes
                # the spec tier back to per-token dispatch
                r"^_spec_body",  # covers the <locals>.body cycle closure
                r"^spec_verify",
                r"^decode_onestep",  # pg + avg_attention decode steps
                # the distilled-narrow-draft spec tier (ISSUE 12): the
                # distillation step loop dispatches once per draft
                # optimizer step, the adaptive host loop dispatches
                # once per draft-verify CYCLE (its single histogram
                # fetch is the sanctioned, suppressed controller
                # input), and the controller's observe/update run
                # between every pair of cycles — a stray sync in any
                # of them serializes the tier back to per-token cost
                r"^DistillTrainer\._distill_steps$",
                r"^run_spec_decode_adaptive$",
                r"^SpecKController\.(observe|update)$",
                # the elastic fleet's router loops (ISSUE 13): tick runs
                # on every router round, the hedge scan walks every
                # in-flight request, and the swap step gates each
                # replica's drain — a host sync in any of them stalls
                # routing (and hedging timing) for the whole fleet
                r"^FleetRouter\.(tick|_hedge_scan|_swap_step"
                r"|_maybe_chaos_kill)$",
                r"^ServingServer\.(_continuous_round|tick_once)$",
                # the serving front door (ISSUE 14): open/admit run on
                # EVERY submit, the leader-done callback on the
                # dispatch thread at resolve time, and the queue's
                # fair-pickup loop once per dequeue — a host sync in
                # any of them serializes admission (or the dispatch
                # loop) for every caller at once
                r"^FrontDoor\.(open|admit_tenant|_leader_done|_close)$",
                r"^SummaryCache\.(get|put)$",
                r"^RequestQueue\.(_put|_pop|_pick_tenant|get"
                r"|get_nowait)$",
                # the fleet telemetry plane (ISSUE 15): the SLO window
                # evaluator runs once per dispatch/router round and its
                # record side inside every future's resolve fan-out;
                # the fleet merge loop runs on every /fleet/* scrape —
                # a stray device sync in either stalls every replica's
                # dispatch (or every scrape) at once
                r"^SloEngine\.(record|evaluate)$",
                r"^merge_fleet_series$",
                r"^Registry\.series$",
                # the performance attribution plane (ISSUE 16): phase
                # timers close on every tick/dispatch, the compile
                # ledger wraps every jitted decode call, and the
                # divergence sentinel judges every priced dispatch — a
                # stray sync in any record path becomes a per-chunk
                # stall on the very path it is supposed to measure
                r"^Profiler\.(start|end|end_wall)$",
                r"^Profiler\.(record_compile|record_hit"
                r"|observe_dispatch)$",
                r"^compiled_call$",
                # ISSUE 17: the process-fleet supervision tick and the
                # remote-handle scrape/rotation reads run at router-tick
                # cadence against every replica — a device sync inside
                # any of them multiplies by fleet size per tick
                r"^ReplicaProcess\.tick$",
                r"^RemoteReplicaHandle\.(healthy|load)$",
                r"^RemoteReplica\.(scrape_healthz|_on_reply|load)$",
                r"^_ReplySource\.rows$",
                r"^ProcFleet\.(supervise_once|_supervise_loop)$",
                # the hierarchical summarizer's fan-out driver (ISSUE
                # 19): _fan_out runs once per document on the submit
                # path, and the chunk-done/record/map-complete/reduce-
                # done chain runs inside the SERVER's resolve callbacks
                # — a host sync in any of them stalls the dispatch
                # thread for every resident request, and the frame
                # assembler feeds on every pipeline row
                r"^HierarchicalSummarizer\.(_fan_out|_chunk_done"
                r"|_record_chunk|_map_complete|_reduce_done)$",
                r"^DocumentAssembler\.feed$",
                # the slot engine's page arena (ISSUE 20): page alloc/free
                # run inside every admission/harvest on the dispatch
                # thread, the engine's page accounting gates every
                # refill, and the arena-occupancy observer fires every
                # tick — pure-numpy by design; a device sync (or a
                # blocking call) in any of them stalls every resident
                # request's chunk cadence
                r"^PageArena\.(alloc|free)$",
                r"^SlotDecodeEngine\.(pages_needed|free_pages"
                r"|arena_stats|_free_slot_pages)$",
                r"^ContinuousBatcher\.(_arena_backpressure"
                r"|_observe_arena)$",
            ],
            # the sanctioned sync windows (metrics flush batches one D2H
            # transfer per metrics_every steps by design)
            "exempt_functions": [r"\._flush_metrics$", r"\._dump_nan_batch$"],
        },
        "TS003": {"enabled": True},
        "TS004": {"enabled": True},
        "TS005": {"enabled": True},
        "TS006": {"enabled": True},
        # -- interprocedural concurrency rules (callgraph.py) --
        "TS007": {"enabled": True},
        "TS008": {
            "enabled": True,
            # dotted call roots that block the calling thread outright
            "blocking_roots": [
                "time.sleep",
                "socket.create_connection",
                "urllib.request.urlopen",
                "subprocess.run", "subprocess.call",
                "subprocess.check_call", "subprocess.check_output",
            ],
            # attribute-call names that block on sockets / processes /
            # events; ``cond.wait()`` on the held lock's own condition
            # is exempted by the rule (it RELEASES that lock)
            "blocking_methods": [
                "recv", "recvfrom", "accept", "connect", "connect_ex",
                "sendall", "communicate", "wait", "urlopen", "sleep",
            ],
        },
        "TS009": {
            "enabled": True,
            # writers matching this run at construction time, before the
            # object escapes to other threads (happens-before via
            # Thread.start) — they don't count as racing accesses
            "init_method_re":
                r"^(__init__|__new__|__post_init__|_init[a-z_]*)$",
        },
        "TS010": {
            "enabled": True,
            # the single sanctioned settle funnel (clause A) and the
            # first-wins guard-flag discipline (clause B)
            "funnel_methods": ["_finish"],
            "settle_flags": ["_settled"],
            "resolver_methods": ["_finish", "_resolve", "_reject"],
        },
    },
}


def merge_config(override: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """DEFAULT deep-merged with `override` (override wins per key; rule
    dicts merge key-by-key rather than wholesale)."""
    cfg = copy.deepcopy(DEFAULT)
    if not override:
        return cfg
    for key, value in override.items():
        if key == "rules" and isinstance(value, dict):
            for rid, rcfg in value.items():
                if isinstance(rcfg, dict):
                    cfg["rules"].setdefault(rid, {}).update(rcfg)
                elif isinstance(rcfg, bool):  # {"TS004": False} shorthand
                    cfg["rules"].setdefault(rid, {})["enabled"] = rcfg
                else:
                    raise ValueError(
                        f"rule config for {rid} must be a dict or bool, "
                        f"got {type(rcfg).__name__}")
        else:
            cfg[key] = value
    return cfg
