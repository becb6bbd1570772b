"""The committed byte-budget regression gate (ISSUE 5; PERF.md 'Byte
diet').

Counts, not speed: they need no chip and say nothing about one.  XLA's
cost model is backend-portable enough to hold the LEVERS accountable on
CPU: this
module compiles the REAL train step (grad + clip + Adagrad) at the small
vocab-dominated gate scale pinned in BYTE_BUDGET.json and asserts, in
tier-1, that

  * each config's bytes accessed stays under its committed budget, and
  * each byte-diet lever (--loss_chunk streaming vocab loss,
    --opt_state_dtype=bfloat16, both) still delivers at least its
    committed reduction vs the baseline config.

Absolute bytes depend on fusion decisions, so budgets carry headroom and
the REDUCTION floors are the real claims (see BYTE_BUDGET.json's
_comment for the re-baselining rule).
"""

import json
import os

import pytest

from textsummarization_on_flink_tpu.config import HParams, derive_draft_hps
from __graft_entry__ import (
    _analytic_step_flops,
    decode_resident_bytes,
    decode_state_bytes,
    decode_step_cost,
    decode_step_flops,
    prefill_cost,
    train_step_comms,
    train_step_cost,
)

BUDGET_PATH = os.path.join(os.path.dirname(__file__), "..",
                           "BYTE_BUDGET.json")


def _cost_bytes(hps: HParams):
    """(bytes accessed, peak temp bytes | None) of the compiled step —
    through the ONE shared compile-and-read helper, so the gate measures
    exactly what BENCH_MODE=bytes and the roofline report."""
    cost = train_step_cost(hps)
    return cost["bytes"], cost["temp_bytes"]


@pytest.fixture(scope="module")
def budget():
    with open(BUDGET_PATH) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def measured(budget):
    """Compile each budgeted config once; ~3-7s per program on CPU (and
    the persistent compile cache makes suite re-runs near-free)."""
    chunk = int(budget["loss_chunk"])
    pg = HParams(**budget["gate_scale"]["pointer_generator"])
    tf = HParams(**budget["gate_scale"]["transformer"])
    configs = {
        "pg_base": pg,
        "pg_losschunk": pg.replace(loss_chunk=chunk),
        "pg_optbf16": pg.replace(opt_state_dtype="bfloat16"),
        "pg_bytediet": pg.replace(loss_chunk=chunk,
                                  opt_state_dtype="bfloat16"),
        "transformer_base": tf,
        "transformer_losschunk": tf.replace(loss_chunk=chunk),
    }
    assert set(configs) == set(budget["budgets"]), (
        "BYTE_BUDGET.json budgets and the gate's config map must cover "
        "the same keys")
    return {name: dict(zip(("bytes", "temp"), _cost_bytes(hps)))
            for name, hps in configs.items()}


_BASE_OF = {
    "pg_losschunk": "pg_base",
    "pg_optbf16": "pg_base",
    "pg_bytediet": "pg_base",
    "transformer_losschunk": "transformer_base",
}


def test_bytes_within_committed_budgets(budget, measured):
    over = {
        name: (c["bytes"], budget["budgets"][name]["max_bytes"])
        for name, c in measured.items()
        if c["bytes"] > budget["budgets"][name]["max_bytes"]
    }
    assert not over, (
        f"bytes-accessed regression past the committed budget: {over} "
        f"(see BYTE_BUDGET.json _comment for the re-baselining rule)")


@pytest.mark.parametrize("lever", sorted(_BASE_OF))
def test_lever_reduction_floors_hold(budget, measured, lever):
    floor = budget["budgets"][lever]["min_reduction_vs_base"]
    base = measured[_BASE_OF[lever]]["bytes"]
    reduction = 1.0 - measured[lever]["bytes"] / base
    assert reduction >= floor, (
        f"{lever}: byte reduction vs {_BASE_OF[lever]} fell to "
        f"{reduction:.1%} (committed floor {floor:.1%}) — the lever "
        f"stopped cutting bytes")


@pytest.mark.parametrize("lever", sorted(
    k for k in _BASE_OF if k.endswith("losschunk")))
def test_peak_temp_floors_hold(budget, measured, lever):
    """PEAK TEMP memory (compiled.memory_analysis()) is fusion- and
    loop-counting-independent: the streaming loss must shrink the live
    set by at least the committed fraction — the direct evidence that
    the [T_dec, B, V] scores value + autodiff residual no longer exist."""
    floor = budget["budgets"][lever]["min_temp_reduction_vs_base"]
    base = measured[_BASE_OF[lever]]["temp"]
    temp = measured[lever]["temp"]
    if base is None or temp is None:
        pytest.skip("backend provides no compiled memory stats")
    reduction = 1.0 - temp / base
    assert reduction >= floor, (
        f"{lever}: peak-temp reduction vs {_BASE_OF[lever]} fell to "
        f"{reduction:.1%} (committed floor {floor:.1%}) — the scores "
        f"residual is materializing again")


# --------------------------------------------------------------------------
# Decode byte diet gate (ISSUE 7; PERF.md "Decode byte diet")
# --------------------------------------------------------------------------
#
# Same contract as the train gate, for the compiled beam SEARCH: the
# committed `decode` section pins bytes-per-emitted-token and peak-temp
# budgets per family and loop kind (plus the step_slots_jit slot kernel)
# against the PRE-PR materialized-history baseline measured before the
# backpointer restructure landed.  A regression that reintroduces
# per-step history gathers fails tier-1 on CPU, hardware or no hardware.

_DECODE_KINDS = ("while", "scan", "chunked", "slot")


def _decode_hps(budget, family: str) -> HParams:
    gs = dict(budget["gate_scale"][family])
    gs.update(budget["decode"]["gate_scale_overrides"])
    return HParams(**gs)


@pytest.fixture(scope="module")
def decode_measured(budget):
    """Compile each budgeted decode config once (~2-5s per program on
    CPU; the persistent compile cache makes suite re-runs near-free)."""
    chunk = int(budget["decode"]["chunk"])
    out = {}
    for family in ("pointer_generator", "transformer"):
        hps = _decode_hps(budget, family)
        out[family] = {
            kind: (decode_step_cost(hps, path="slot", chunk=chunk)
                   if kind == "slot"
                   else decode_step_cost(
                       hps, loop=kind,
                       chunk=chunk if kind == "chunked" else None))
            for kind in _DECODE_KINDS
        }
    return out


def test_decode_budget_covers_every_kind(budget):
    dec = budget["decode"]
    for family in ("pointer_generator", "transformer"):
        assert set(dec["budgets"][family]) == set(_DECODE_KINDS)
        assert set(dec["baseline"][family]) == set(_DECODE_KINDS)


@pytest.mark.parametrize("family", ["pointer_generator", "transformer"])
def test_decode_bytes_per_token_within_budgets(budget, decode_measured,
                                               family):
    budgets = budget["decode"]["budgets"][family]
    over = {
        kind: (c["bytes_per_token"], budgets[kind]["max_bytes_per_token"])
        for kind, c in decode_measured[family].items()
        if c["bytes_per_token"] > budgets[kind]["max_bytes_per_token"]
    }
    assert not over, (
        f"{family}: decode bytes-per-token regression past the committed "
        f"budget: {over} (see BYTE_BUDGET.json decode._comment for the "
        f"re-baselining rule)")


@pytest.mark.parametrize("family", ["pointer_generator", "transformer"])
@pytest.mark.parametrize("kind", _DECODE_KINDS)
def test_decode_reduction_floors_hold(budget, decode_measured, family, kind):
    """The backpointer-history claim: per-step search traffic dropped vs
    the committed pre-PR (materialized-history) baseline and stays
    dropped — >=25% bytes/token for every pointer-generator loop kind
    (the ISSUE 7 acceptance floor), transformer floors from
    measurement."""
    floor = budget["decode"]["budgets"][family][kind]["min_reduction_vs_base"]
    base = budget["decode"]["baseline"][family][kind]["bytes_per_token"]
    reduction = 1.0 - decode_measured[family][kind]["bytes_per_token"] / base
    assert reduction >= floor, (
        f"{family}/{kind}: decode bytes-per-token reduction vs the pre-PR "
        f"baseline fell to {reduction:.1%} (committed floor {floor:.1%}) — "
        f"per-hypothesis history traffic is back")


@pytest.mark.parametrize("family", ["pointer_generator", "transformer"])
@pytest.mark.parametrize("kind", _DECODE_KINDS)
def test_decode_peak_temp_floors_hold(budget, decode_measured, family, kind):
    """Peak live-temp is the fusion- and loop-counting-independent
    evidence the [K, T, T_enc] trajectory buffers (live + result pool +
    candidate intermediates) no longer exist as materialized state."""
    floor = budget["decode"]["budgets"][family][kind][
        "min_temp_reduction_vs_base"]
    base = budget["decode"]["baseline"][family][kind]["temp_bytes"]
    temp = decode_measured[family][kind]["temp_bytes"]
    if temp is None:
        pytest.skip("backend provides no compiled memory stats")
    reduction = 1.0 - temp / base
    assert reduction >= floor, (
        f"{family}/{kind}: decode peak-temp reduction vs the pre-PR "
        f"baseline fell to {reduction:.1%} (committed floor {floor:.1%}) — "
        f"the trajectory buffers are materializing again")


# --------------------------------------------------------------------------
# Prefill/decode disaggregation gate (ISSUE 11)
# --------------------------------------------------------------------------
#
# Two committed claims (BYTE_BUDGET.json decode.length_axis /
# decode.prefill): (1) the length-masked slot chunk's cost scales with
# the longest active resident's TRUE article length (the traced block
# chain — decode_step_cost's enc_len axis prices exactly the blocks the
# served program executes at that length); (2) the prefill stage's
# encoder work scales with the article's BUCKET instead of the full
# max_enc_steps every admission used to pay.

_DISAGG_FAMILIES = ("pointer_generator", "transformer")


@pytest.fixture(scope="module")
def length_axis_measured(budget):
    la = budget["decode"]["length_axis"]
    chunk = int(budget["decode"]["chunk"])
    out = {}
    for family in _DISAGG_FAMILIES:
        hps = _decode_hps(budget, family).replace(
            decode_enc_block=int(la["enc_block"]))
        out[family] = {
            int(L): decode_step_cost(hps, path="slot", chunk=chunk,
                                     enc_len=int(L))
            for L in la["lengths"]
        }
    return out


@pytest.fixture(scope="module")
def prefill_measured(budget):
    pf = budget["decode"]["prefill"]
    out = {}
    for family in _DISAGG_FAMILIES:
        hps = _decode_hps(budget, family)
        out[family] = {int(b): prefill_cost(hps, int(b))
                       for b in pf["buckets"]}
    return out


@pytest.mark.parametrize("family", _DISAGG_FAMILIES)
def test_length_axis_bytes_within_budgets(budget, length_axis_measured,
                                          family):
    budgets = budget["decode"]["length_axis"]["budgets"][family]
    over = {
        L: (c["bytes_per_token"], budgets["max_bytes_per_token"][str(L)])
        for L, c in length_axis_measured[family].items()
        if c["bytes_per_token"] > budgets["max_bytes_per_token"][str(L)]
    }
    assert not over, (
        f"{family}: masked-slot bytes/token past the committed budget at "
        f"{over} (see BYTE_BUDGET.json decode.length_axis._comment)")


@pytest.mark.parametrize("family", _DISAGG_FAMILIES)
def test_length_axis_cost_scales_with_true_length(budget,
                                                  length_axis_measured,
                                                  family):
    """The acceptance claim: a chunk whose longest active resident is a
    T_enc/4 (or T_enc/2) article costs at most the committed ratio of
    the full-length chunk — cost follows TRUE length, not padding."""
    la = budget["decode"]["length_axis"]
    full_len = max(int(L) for L in la["lengths"])
    full = length_axis_measured[family][full_len]["bytes_per_token"]
    for L, ceiling in la["budgets"][family]["max_ratio_vs_full"].items():
        ratio = length_axis_measured[family][int(L)]["bytes_per_token"] \
            / full
        assert ratio <= ceiling, (
            f"{family}: masked-slot bytes/token at length {L} is "
            f"{ratio:.3f}x the full-length chunk (committed max "
            f"{ceiling}) — decode cost is following padding again")


@pytest.mark.parametrize("family", _DISAGG_FAMILIES)
def test_length_axis_beats_uniform_padding_baseline(budget,
                                                    length_axis_measured,
                                                    family):
    """Reduction floors vs the PRE-CHANGE uniform-padding slot step
    (every resident paid full-width cross-attention regardless of
    article length, measured before disaggregation landed)."""
    la = budget["decode"]["length_axis"]
    uniform = la["uniform_baseline"][family]
    floors = la["budgets"][family]["min_reduction_vs_uniform"]
    for L, floor in floors.items():
        got = length_axis_measured[family][int(L)]["bytes_per_token"]
        reduction = 1.0 - got / uniform
        assert reduction >= floor, (
            f"{family}: masked-slot reduction vs the uniform-padding "
            f"baseline at length {L} fell to {reduction:.1%} (committed "
            f"floor {floor:.1%})")


@pytest.mark.parametrize("family", _DISAGG_FAMILIES)
def test_length_axis_is_monotone(length_axis_measured, family):
    """Longer max-active-resident lengths can only cost more — the
    block chain has no pathological cliffs."""
    costs = [length_axis_measured[family][L]["bytes_per_token"]
             for L in sorted(length_axis_measured[family])]
    assert costs == sorted(costs), costs


@pytest.mark.parametrize("family", _DISAGG_FAMILIES)
def test_prefill_cost_scales_with_bucket(budget, prefill_measured, family):
    """Quarter-bucket prefill under the committed ratios of the
    pre-change full-width pack (encoder at max_enc_steps on EVERY
    admission) — bytes AND flops — plus monotonicity in the bucket."""
    pf = budget["decode"]["prefill"]
    base = pf["uniform_pack_baseline"][family]
    limits = pf["budgets"][family]
    quarter = min(prefill_measured[family])
    got = prefill_measured[family][quarter]
    byte_ratio = got["bytes"] / base["bytes"]
    flops_ratio = got["flops"] / base["flops"]
    assert byte_ratio <= limits["max_bytes_ratio_quarter"], (
        f"{family}: quarter-bucket prefill bytes are {byte_ratio:.3f}x "
        f"the pre-change full-width pack (committed max "
        f"{limits['max_bytes_ratio_quarter']}) — the encoder stage is "
        f"paying padded width again")
    assert flops_ratio <= limits["max_flops_ratio_quarter"], (
        f"{family}: quarter-bucket prefill flops are {flops_ratio:.3f}x "
        f"the pre-change full-width pack (committed max "
        f"{limits['max_flops_ratio_quarter']})")
    buckets = sorted(prefill_measured[family])
    for axis in ("bytes", "flops"):
        vals = [prefill_measured[family][b][axis] for b in buckets]
        assert vals == sorted(vals), (family, axis, vals)


# --------------------------------------------------------------------------
# Paged resident-state gate (ISSUE 20; SERVING.md "Paged resident state")
# --------------------------------------------------------------------------
#
# The committed `decode.resident` section pins what one ADMITTED slot
# holds in HBM via decode_resident_bytes (eval_shape accounting of the
# REAL init_slots_jit state) at the decode gate scale: the full-length
# reservation (what every slot holds under the default arena) is
# re-measured and pinned, the per-slot cost at the bimodal mix stays
# under its ceiling, and the reduction floors — the "HBM holds more
# residents" claim priced per slot — hold.


@pytest.fixture(scope="module")
def resident_measured(budget):
    rs = budget["decode"]["resident"]
    out = {}
    for family in _DISAGG_FAMILIES:
        hps = _decode_hps(budget, family).replace(
            decode_enc_block=int(rs["enc_block"]))
        out[family] = decode_resident_bytes(
            hps, pages=int(rs["arena_pages"]), mix=rs["mix"])
    return out


@pytest.mark.parametrize("family", _DISAGG_FAMILIES)
def test_resident_full_length_baseline_pinned(budget, resident_measured,
                                              family):
    """The comparison cannot drift silently: the re-measured
    full-length per-slot bytes must sit within full_length_slack of the
    committed figure (eval_shape is deterministic — a move here means
    the slot state itself changed, which requires re-baselining IN THE
    SAME COMMIT)."""
    rs = budget["decode"]["resident"]
    committed = rs["baseline"][family]["full_length_bytes_per_slot"]
    got = resident_measured[family]["full_length_bytes_per_slot"]
    slack = rs["full_length_slack"]
    assert abs(got - committed) <= slack * committed, (
        f"{family}: full-length resident bytes/slot moved to {got} "
        f"(committed {committed} ± {slack:.0%}) — the SlotState changed "
        f"under the comparison (see BYTE_BUDGET.json "
        f"decode.resident._comment)")


@pytest.mark.parametrize("family", _DISAGG_FAMILIES)
def test_resident_bytes_within_budget(budget, resident_measured, family):
    ceiling = budget["decode"]["resident"]["budgets"][family][
        "max_bytes_per_slot"]
    got = resident_measured[family]["bytes_per_slot"]
    assert got <= ceiling, (
        f"{family}: resident bytes/slot at the bimodal mix rose to "
        f"{got} (committed ceiling {ceiling}) — the fixed share or the "
        f"page grew (see BYTE_BUDGET.json decode.resident._comment)")


@pytest.mark.parametrize("family", _DISAGG_FAMILIES)
def test_resident_reduction_floor_holds(budget, resident_measured, family):
    """The headline claim per slot: at the bimodal mix, a resident
    holds at least the committed fraction less HBM than a full-length
    one — the capacity a tight arena converts into extra residents
    (the serving-level half is SERVE_SLO.json 'paged')."""
    floor = budget["decode"]["resident"]["budgets"][family][
        "min_reduction_vs_full_length"]
    full = resident_measured[family]["full_length_bytes_per_slot"]
    at_mix = resident_measured[family]["bytes_per_slot"]
    reduction = 1.0 - at_mix / full
    assert reduction >= floor, (
        f"{family}: resident reduction at the mix against the full "
        f"length fell to {reduction:.1%} (committed floor {floor:.1%}) "
        f"— paging no longer buys resident capacity at the bimodal mix")


@pytest.mark.parametrize("family", _DISAGG_FAMILIES)
def test_resident_accounting_is_structural(resident_measured, family):
    """Honesty check on the accounting itself: the pooled leaves of the
    SlotState must price to exactly (arena_pages + 1 scratch) x
    page_bytes — i.e. page_bytes really is the marginal HBM cost of one
    admitted page, not a model."""
    rb = resident_measured[family]
    pools = rb["total_bytes"] \
        - rb["fixed_bytes_per_slot"] * rb["slots"]
    assert pools == (rb["arena_pages"] + 1) * rb["page_bytes"], rb
# --------------------------------------------------------------------------
#
# The committed `spec` section pins the draft tier's per-token cost
# against the full model (FLOPs ratio ceilings from cost_analysis), the
# AAN family's O(1)-in-history resident state, and the honesty of the
# committed acceptance-rate -> expected-speedup curve (recomputed from
# the bandwidth-model formula at the committed reference-scale analytic
# ratio).  See BYTE_BUDGET.json spec._comment for the ceilings' story
# and the stated kill condition.


def _spec_hps(budget, family: str) -> HParams:
    gs = dict(budget["gate_scale"][family])
    gs.update(budget["decode"]["gate_scale_overrides"])
    hps = HParams(**gs).replace(spec_k=int(budget["spec"]["spec_k"]),
                                **budget["spec"]["draft_overrides"])
    hps.validate()
    return hps


@pytest.fixture(scope="module")
def spec_measured(budget):
    """One decode_step_flops call per family (~4 small step compiles
    each; the persistent compile cache makes re-runs near-free)."""
    return {family: decode_step_flops(_spec_hps(budget, family))
            for family in ("pointer_generator", "transformer")}


@pytest.mark.parametrize("family", ["pointer_generator", "transformer"])
def test_spec_draft_flops_ratio_within_ceiling(budget, spec_measured,
                                               family):
    ceiling = budget["spec"]["max_draft_flops_ratio"][family]
    got = spec_measured[family]["draft_full_ratio"]
    assert got <= ceiling, (
        f"{family}: draft/full decode-step FLOPs ratio rose to "
        f"{got:.3f} (committed ceiling {ceiling}) — the draft tier "
        f"stopped being cheap (see BYTE_BUDGET.json spec._comment)")


@pytest.mark.parametrize("family", ["pointer_generator", "transformer"])
def test_spec_draft_state_ratio_within_ceiling(budget, spec_measured,
                                               family):
    ceiling = budget["spec"]["max_draft_state_ratio"][family]
    got = spec_measured[family]["draft_state_ratio"]
    assert got <= ceiling, (
        f"{family}: draft/full resident decode-state ratio rose to "
        f"{got:.4f} (committed ceiling {ceiling}) — the AAN slot-cost "
        f"advantage eroded")


def test_spec_draft_state_is_o1_in_history(budget):
    """THE AAN claim: the draft's resident decode state does not grow
    with max_dec_steps (the transformer's KV cache does — sanity-check
    that the measurement isn't vacuous)."""
    hps = _spec_hps(budget, "transformer")
    draft = derive_draft_hps(hps).replace(beam_size=1, mode="decode")
    short = decode_state_bytes(draft)
    long_ = decode_state_bytes(draft.replace(max_dec_steps=4 * hps.max_dec_steps))
    assert short == long_, (
        f"AAN draft decode state grew with max_dec_steps "
        f"({short} -> {long_} bytes): the O(1)-in-history property is "
        f"gone — a history-sized buffer crept into the adapter state")
    full_short = decode_state_bytes(hps.replace(beam_size=1))
    full_long = decode_state_bytes(
        hps.replace(beam_size=1, max_dec_steps=4 * hps.max_dec_steps))
    assert full_long > full_short  # the contrast that makes O(1) a win


def test_spec_expected_speedup_curve_is_honest(budget):
    """The committed acceptance->speedup curve must equal the model
    formula evaluated at the committed REFERENCE-scale analytic
    draft/full ratio, and that ratio must still be reproduced by the
    analytic step-FLOPs model (no compile — instant).  Ref scale uses
    ``ref_overrides`` (H/2-wide draft, rank-64 factored head — the
    distilled-narrow-draft recipe at H=256), not the gate-scale
    ``draft_overrides``."""
    from textsummarization_on_flink_tpu.decode.speculative import (
        expected_speedup,
    )

    spec = budget["spec"]
    k = int(spec["spec_k"])
    ref = HParams(model_family="transformer",
                  **spec["ref_overrides"])
    got_ratio = (_analytic_step_flops(derive_draft_hps(ref))
                 / _analytic_step_flops(ref))
    want_ratio = spec["ref_analytic_ratio"]["transformer"]
    assert abs(got_ratio - want_ratio) < 0.005, (
        f"reference-scale analytic draft/full ratio drifted to "
        f"{got_ratio:.4f} (committed {want_ratio}) — re-baseline the "
        f"spec section (and PERF.md) or fix the regression")
    for alpha, want in spec["expected_speedup"]["transformer"].items():
        recomputed = expected_speedup(float(alpha), k, want_ratio)
        assert abs(recomputed - want) / want < 0.02, (
            f"committed expected_speedup[{alpha}]={want} no longer "
            f"matches the formula ({recomputed:.4f}) — the curve and "
            f"the model drifted apart")


def test_spec_narrow_draft_meets_issue12_bar(budget):
    """The ISSUE-12 acceptance bar, pinned against the committed
    numbers themselves: the transformer draft/full FLOPs ceiling is at
    most 0.5 (down from the equal-width 0.95), the ref-scale analytic
    ratio sits under it, and the re-pinned curve's FLOPs break-even
    reaches 0.5 acceptance (speedup >= 1 there — the equal-width draft
    managed 0.42)."""
    spec = budget["spec"]
    assert spec["max_draft_flops_ratio"]["transformer"] <= 0.5
    assert spec["ref_analytic_ratio"]["transformer"] <= \
        spec["max_draft_flops_ratio"]["transformer"]
    assert spec["expected_speedup"]["transformer"]["0.5"] >= 1.0


def test_spec_verify_scores_positions_cheaper_than_steps(budget,
                                                         spec_measured):
    """The 'one fat step' claim: the parallel verify's per-position
    FLOPs must not exceed the incremental greedy step's (it amortizes
    the cache scatter and shares one pass)."""
    m = spec_measured["transformer"]
    assert m["verify_flops_per_position"] is not None
    assert m["verify_flops_per_position"] <= m["tiers"]["greedy"]["flops"], (
        f"parallel verify costs {m['verify_flops_per_position']:.0f} "
        f"FLOPs/position vs {m['tiers']['greedy']['flops']:.0f} for an "
        f"incremental step — the batched pass lost its advantage")


# --------------------------------------------------------------------------
# One-mesh comms gate (ISSUE 8; PERF.md "One mesh")
# --------------------------------------------------------------------------
#
# The unified sharded step's per-step collective bytes, enforced per mesh
# shape from the committed `comms` section: on wire=bf16 meshes the
# dp-axis all-reduce must move exactly the registry-predicted gradient
# elements (the retired lowp shard_map path's reduction set), priced at
# the registry wire dtype; tp overhead stays under committed ceilings.


@pytest.fixture(scope="module")
def comms_measured(budget):
    """Compile the unified step once per committed mesh shape (~3-6s
    each on CPU; persistent compile cache makes re-runs near-free)."""
    gs = budget["gate_scale"]["pointer_generator"]
    out = {}
    for name, entry in budget["comms"]["meshes"].items():
        hps = HParams(**gs).replace(**entry["overrides"])
        hps.validate()
        out[name] = train_step_comms(hps)
    return out


def test_comms_ref_scale_analytic_pins_lowp_wire_bytes(budget):
    """The headline equality: at reference scale the unified step's dp
    gradient wire carries the retired lowp path's committed 43.0 MB/step
    under the bf16 annotation (86.0 at f32) — registry analytics, no
    compile."""
    from textsummarization_on_flink_tpu.parallel import (
        sharding as sharding_lib,
    )

    ref = budget["comms"]["ref_dp_wire_mb"]
    for wire, want_mb in ref.items():
        hps = HParams(batch_size=16, compute_dtype="bfloat16",
                      grad_allreduce_dtype=wire)
        got = sharding_lib.analytic_comms(hps)["dp_wire_bytes"] / 1e6
        assert round(got, 1) == want_mb, (
            f"analytic ref-scale dp wire bytes at {wire} drifted to "
            f"{got:.2f} MB (committed {want_mb}) — the registry's "
            f"reduction set no longer matches the retired lowp path's")


@pytest.mark.parametrize("mesh_name", ["dp4_bf16", "dp2_tp2_bf16"])
def test_comms_dp_elements_match_registry_exactly(budget, comms_measured,
                                                  mesh_name):
    """Wire-annotated meshes reduce EXACTLY the registry's predicted
    gradient elements over dp (slack covers only the scalar metric
    pmeans): nothing double-reduced, nothing skipped, on pure-dp AND
    dp x tp — the restriction the shard_map step had is gone."""
    slack = budget["comms"]["element_slack"]
    c = comms_measured[mesh_name]
    want = c["analytic"]["dp_grad_elements"]
    got = c["dp"]["elements"]
    assert want <= got <= want + slack, (
        f"{mesh_name}: dp all-reduce moves {got} elements/step, registry "
        f"predicts {want} (+{slack} scalar slack) — the unified step's "
        f"reduction set drifted from the registry spec")


@pytest.mark.parametrize("mesh_name", ["dp4_bf16", "dp2_tp2_bf16",
                                       "dp2_tp2_f32"])
def test_comms_wire_bytes_within_ceilings(budget, comms_measured, mesh_name):
    entry = budget["comms"]["meshes"][mesh_name]
    c = comms_measured[mesh_name]
    assert c["dp_wire_bytes"] <= entry["max_dp_wire_bytes"], (
        f"{mesh_name}: dp wire bytes {c['dp_wire_bytes']} over the "
        f"committed ceiling {entry['max_dp_wire_bytes']}")
    assert c["tp"]["bytes_hlo"] <= entry["max_tp_bytes_hlo"], (
        f"{mesh_name}: tp collective bytes {c['tp']['bytes_hlo']} over "
        f"the committed ceiling {entry['max_tp_bytes_hlo']}")


def test_comms_no_stray_axes(budget, comms_measured):
    """No sp or mixed-group collectives on the committed meshes: every
    collective is attributable to the axis the registry assigns it."""
    for name, c in comms_measured.items():
        assert c["sp"]["instructions"] == 0, (name, c["sp"])
        assert c["mixed"]["instructions"] == 0, (name, c["mixed"])


def test_comms_bf16_wire_halves_dp_bytes(comms_measured):
    """The annotation is the lever: same mesh, same reduction set —
    wire bytes halve from f32 to bf16 (identical element counts would
    be ideal, but the f32 path lets GSPMD pick its own reduction
    placement, so assert the priced ratio on the registry analytics)."""
    b = comms_measured["dp2_tp2_bf16"]["analytic"]
    f = comms_measured["dp2_tp2_f32"]["analytic"]
    assert b["dp_grad_elements"] == f["dp_grad_elements"]
    assert b["dp_wire_bytes"] * 2 == f["dp_wire_bytes"]


def test_base_configs_are_vocab_dominated(budget, measured):
    """The gate scale must keep the scores tensor the dominant byte sink
    (that is what makes it a stand-in for reference scale): the
    streaming-loss saving must exceed one full copy of the f32 scores
    tensor, i.e. the lever removed value+residual traffic, not noise."""
    # T_dec * B * V * 4 bytes: one copy of the f32 scores tensor
    gs = budget["gate_scale"]["pointer_generator"]
    one_scores = (gs["max_dec_steps"] * gs["batch_size"]
                  * gs["vocab_size"] * 4)
    saved = measured["pg_base"]["bytes"] - measured["pg_losschunk"]["bytes"]
    assert saved > one_scores, (
        f"streaming loss saved {saved / 1e6:.1f} MB, less than ONE copy "
        f"of the scores tensor ({one_scores / 1e6:.1f} MB) — the value "
        f"+ residual elimination claim does not hold")
