"""Differential tests for the trace→Workload lowering (repro.trace).

Three tiers:

* Golden-fixture replay (jax-free): the committed TraceGraph JSONs under
  ``tests/fixtures/trace/`` lower to Workloads that are bit-exact on MVM
  totals against the hand-built sibling DAGs, with the elementwise
  surplus pinned to an explicit constant so drift is visible.
* Live capture (needs jax): every LM config in :mod:`repro.configs` and
  the CNN references trace → lower → diff bit-exact, and the captured
  graph digest reproduces the committed fixture's.
* Property tests (hypothesis, via the shim): randomly shaped
  weight-chain graphs lower to DAGs whose MVM totals match the analytic
  closed form, topo-sort cleanly, and simulate under all three schedule
  policies with non-negative costs.
"""
import os
import warnings

import pytest

from _hypothesis_shim import given, settings, st

from repro.configs import get_config, list_archs
from repro.core import (OpNode, SchedulePolicy, Workload, default_mapping,
                        lm_workload, simulate, usecase_arch)
from repro.core.costmodel import op_class
from repro.core.schedule import POLICIES
from repro.core.workload import MODEL_BUILDERS
from repro.core import workload as workload_mod
from repro.trace import (TraceEqn, TraceGraph, TraceVar, diff_workloads,
                         lower_graph, summarize)

FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "fixtures", "trace")

# digest prefixes of the committed golden graphs: capture determinism is
# part of the contract (same program + shapes → same content key)
FIXTURE_DIGESTS = {
    "lm_llama3-8b_forward.json": "2016002579c08628",
    "lm_llama3-8b_prefill.json": "a84ca84c0be87312",
    "lm_llama3-8b_decode.json": "0501a7a36bdf342c",
    "lm_dbrx-132b_forward.json": "059e480658e1e6d7",
    "cnn_resnet18_32.json": "3c7700ddc169e4e1",
}


def _fixture(name: str) -> TraceGraph:
    return TraceGraph.load(os.path.join(FIXTURE_DIR, name))


def _hand_for(graph: TraceGraph) -> Workload:
    meta = graph.meta
    if "config" in meta:
        return lm_workload(get_config(meta["config"]),
                           seq_len=int(meta["seq_len"]),
                           batch=int(meta["batch"]))
    return MODEL_BUILDERS[meta["model"]](int(meta["img"]),
                                         int(meta["num_classes"]))


# ---------------------------------------------------------------------------
# Golden-fixture replay (jax-free)
# ---------------------------------------------------------------------------

def test_fixture_set_is_committed():
    missing = [n for n in FIXTURE_DIGESTS if not
               os.path.exists(os.path.join(FIXTURE_DIR, n))]
    assert not missing, f"golden fixtures missing: {missing}"


@pytest.mark.parametrize("name", sorted(FIXTURE_DIGESTS))
def test_fixture_digest_stable(name):
    g = _fixture(name)
    assert g.digest().startswith(FIXTURE_DIGESTS[name])
    # serialisation round-trips content-identically
    assert TraceGraph.from_dict(g.to_dict()).digest() == g.digest()


@pytest.mark.parametrize("name", [n for n in sorted(FIXTURE_DIGESTS)
                                  if "decode" not in n])
def test_fixture_differential(name):
    traced = lower_graph(_fixture(name))
    d = diff_workloads(traced, _hand_for(_fixture(name)))
    assert d["mvm_match"], d
    assert d["total_weights_equal"], d


def test_llama3_forward_fixture_pinned():
    """The flagship fixture's totals, as explicit numbers: MVM macs and
    weights bit-exact vs the hand DAG, elementwise surplus pinned so a
    lowering change shows up as a diff of THIS constant."""
    traced = lower_graph(_fixture("lm_llama3-8b_forward.json"))
    hand = lm_workload(get_config("llama3-8b"), seq_len=8, batch=1)
    d = diff_workloads(traced, hand)
    assert d["traced"]["mvm_macs"] == 60_054_044_672
    assert d["traced"]["mvm_macs"] == d["hand"]["mvm_macs"]
    assert d["traced"]["mvm_weights"] == 743_440_384
    assert d["traced"]["mvm_weights"] == d["hand"]["mvm_weights"]
    assert d["elementwise_surplus"] == 13_459_520
    assert traced.source_digest.startswith("2016002579c08628")


def test_dbrx_moe_fixture_pinned():
    d = diff_workloads(lower_graph(_fixture("lm_dbrx-132b_forward.json")),
                       _hand_for(_fixture("lm_dbrx-132b_forward.json")))
    assert d["traced"]["mvm_macs"] == 286_852_644_864
    assert d["mvm_match"] and d["total_weights_equal"]
    assert d["elementwise_surplus"] == 55_711_488


def test_resnet18_fixture_pinned():
    d = diff_workloads(lower_graph(_fixture("cnn_resnet18_32.json")),
                       _hand_for(_fixture("cnn_resnet18_32.json")))
    assert d["traced"]["mvm_macs"] == 555_468_800
    assert d["mvm_match"] and d["total_weights_equal"]
    assert d["elementwise_surplus"] == 492_032


def test_decode_fixture_lowers_and_orders():
    """Decode has no hand sibling (lm_workload models a full sequence);
    the contract is that it lowers, topo-sorts, and carries the KV-cache
    attention matmuls."""
    w = lower_graph(_fixture("lm_llama3-8b_decode.json"))
    order = w.topo_order()
    assert sorted(order) == sorted(w.nodes)
    assert w.levels()
    s = summarize(w)
    assert s["n_mvm"] > 0 and s["mvm_macs"] > 0
    kinds = {n.kind for n in w.nodes.values()}
    assert "matmul" in kinds and "fc" in kinds


@pytest.mark.parametrize("policy", POLICIES)
def test_traced_fixtures_simulate_under_every_policy(policy):
    """Traced DAGs run through the unmodified scheduler: every committed
    fixture simulates under all three policies with no warnings and
    strictly positive cost."""
    arch = usecase_arch(16)
    mapping = default_mapping(arch, "spatial")
    for name in sorted(FIXTURE_DIGESTS):
        w = lower_graph(_fixture(name))
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            rep = simulate(arch, w, mapping, schedule=SchedulePolicy(policy))
        assert rep.latency_cycles > 0
        assert rep.total_energy_uj > 0


def test_partitioned_beats_monolithic_on_traced_cnn():
    """The traced resnet18 DAG has real branch concurrency the scheduler
    can exploit — partitioned must not be slower than monolithic."""
    arch = usecase_arch(16)
    mapping = default_mapping(arch, "spatial")
    lat = {}
    for pol in ("monolithic", "partitioned"):
        w = lower_graph(_fixture("cnn_resnet18_32.json"))
        lat[pol] = simulate(arch, w, mapping,
                            schedule=SchedulePolicy(pol)).latency_cycles
    assert lat["partitioned"] <= lat["monolithic"]


def test_source_digest_keys_the_explore_cache():
    from repro.explore.job import content_key

    g = _fixture("lm_llama3-8b_forward.json")
    w1, w2 = lower_graph(g), lower_graph(g)
    assert w1.source_digest == g.digest()
    assert content_key(w1) == content_key(w2)
    w2.source_digest = "0" * 64
    assert content_key(w1) != content_key(w2)
    # hand-built workloads (source_digest=None) still canonicalise
    hand = lm_workload(get_config("llama3-8b"), seq_len=8)
    assert hand.source_digest is None
    assert content_key(hand) != content_key(w1)


# ---------------------------------------------------------------------------
# Unknown-kind fallback (satellite regression)
# ---------------------------------------------------------------------------

def _one_off_workload(kind: str) -> Workload:
    w = Workload(f"oneoff-{kind}")
    w.fc("fc", 64, 64)
    w.simple("tail", kind, 4096, inputs=("fc",))
    return w


def test_unknown_kind_warns_once_and_prices_as_elementwise():
    arch = usecase_arch(4)
    mapping = default_mapping(arch, "spatial")
    workload_mod._warned_kinds.discard("frobnicate")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        odd = simulate(arch, _one_off_workload("frobnicate"), mapping)
    msgs = [str(x.message) for x in rec
            if issubclass(x.category, RuntimeWarning)]
    assert any("frobnicate" in m for m in msgs), msgs
    # an unknown kind is priced exactly like a known elementwise op of
    # the same element count — never silently free, never a crash
    act = simulate(arch, _one_off_workload("act"), mapping)
    assert odd.latency_cycles == act.latency_cycles
    assert odd.total_energy_uj == act.total_energy_uj
    # the warning fires once per kind per process
    with warnings.catch_warnings(record=True) as rec2:
        warnings.simplefilter("always")
        simulate(arch, _one_off_workload("frobnicate"), mapping)
    assert not [x for x in rec2 if "frobnicate" in str(x.message)]


def test_known_kinds_never_warn():
    arch = usecase_arch(4)
    mapping = default_mapping(arch, "spatial")
    for kind in sorted(workload_mod.OTHER_KINDS):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            simulate(arch, _one_off_workload(kind), mapping)


def test_weight_free_matmul_classes_as_attention():
    ctx = OpNode(name="ctx", kind="matmul", K=8, N=8, V=64,
                 prunable=False, weight_count=0)
    assert op_class(ctx) == "attention"
    mm = OpNode(name="proj", kind="matmul", K=8, N=8, V=64)
    assert op_class(mm) == "matmul"


# ---------------------------------------------------------------------------
# Live capture (needs jax)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("step", ["forward", "prefill"])
@pytest.mark.parametrize("config", list_archs())
def test_live_lm_differential(config, step):
    """Every LM config, traced live: MVM totals bit-exact vs the hand
    DAG — the acceptance criterion of the tracer."""
    pytest.importorskip("jax", exc_type=ImportError)
    from repro.trace import traced_workload

    traced = traced_workload(config, step=step, seq_len=8, batch=1)
    hand = lm_workload(get_config(config), seq_len=8, batch=1)
    d = diff_workloads(traced, hand)
    assert d["mvm_match"], (config, step, d)
    assert d["total_weights_equal"], (config, step, d)


@pytest.mark.parametrize("config", list_archs())
def test_live_decode_lowers_and_simulates(config):
    pytest.importorskip("jax", exc_type=ImportError)
    from repro.trace import traced_workload

    w = traced_workload(config, step="decode", seq_len=8, batch=1)
    assert sorted(w.topo_order()) == sorted(w.nodes)
    rep = simulate(usecase_arch(16), w,
                   default_mapping(usecase_arch(16), "spatial"))
    assert rep.latency_cycles > 0


@pytest.mark.parametrize("model", ["vgg16", "resnet18"])
def test_live_cnn_differential(model):
    pytest.importorskip("jax", exc_type=ImportError)
    from repro.trace import traced_cnn

    traced = traced_cnn(model, 32, 100)
    d = diff_workloads(traced, MODEL_BUILDERS[model](32, 100))
    assert d["mvm_match"], (model, d)
    assert d["total_weights_equal"], (model, d)
    if model == "vgg16":
        # the straight-line VGG reference folds perfectly: zero surplus
        assert d["elementwise_surplus"] == 0


def test_live_capture_reproduces_committed_digest():
    pytest.importorskip("jax", exc_type=ImportError)
    from repro.trace.capture import trace_model

    g = trace_model(get_config("llama3-8b"), step="forward",
                    seq_len=8, batch=1)
    assert g.digest() == _fixture("lm_llama3-8b_forward.json").digest()


def test_live_model_source_captures():
    """source='model' traces the real execution-plane transformer; the
    diff is informational (flash tiling reshapes the arithmetic), but the
    lowering itself must hold: MVM macs within a few percent of hand."""
    pytest.importorskip("jax", exc_type=ImportError)
    from repro.trace import traced_workload

    traced = traced_workload("llama3-8b", step="forward", seq_len=8,
                             batch=1, source="model")
    hand = lm_workload(get_config("llama3-8b"), seq_len=8, batch=1)
    ratio = traced.total_macs() / hand.total_macs()
    assert 0.9 < ratio < 1.2, ratio


# ---------------------------------------------------------------------------
# Property tests: random weight-chain graphs (hypothesis via the shim)
# ---------------------------------------------------------------------------

_EW_PRIMS = ("exp", "tanh", "logistic", "neg", "sqrt", "abs")


def _chain_graph(n_layers, d, seq, ew_tail):
    """A jaxpr-shaped graph: x(1,seq,d) through n_layers of
    dot_general(·, w_i(d,d)) each followed by ``ew_tail`` unary
    elementwise ops.  Closed-form totals: macs = n_layers·d²·seq,
    weights = n_layers·d²."""
    vars_ = {"x": TraceVar((1, seq, d), "float32")}
    weights, eqns, invars = {}, [], ["x"]
    cur = "x"
    for i in range(n_layers):
        wv = f"w{i}"
        vars_[wv] = TraceVar((d, d), "float32")
        weights[wv] = f"layer{i}/w"
        invars.append(wv)
        out = f"y{i}"
        vars_[out] = TraceVar((1, seq, d), "float32")
        eqns.append(TraceEqn("dot_general", [cur, wv], [out], params={
            "dimension_numbers": [[[2], [0]], [[], []]]}))
        cur = out
        for j, prim in enumerate(ew_tail):
            nxt = f"e{i}_{j}"
            vars_[nxt] = TraceVar((1, seq, d), "float32")
            eqns.append(TraceEqn(prim, [cur], [nxt]))
            cur = nxt
    return TraceGraph(name="prop-chain", invars=invars, outvars=[cur],
                      vars=vars_, eqns=eqns, weights=weights)


@given(n_layers=st.integers(1, 4), d=st.integers(4, 48),
       seq=st.integers(1, 16),
       ew_tail=st.lists(st.sampled_from(_EW_PRIMS), max_size=4))
@settings(max_examples=40, deadline=None)
def test_random_chain_lowers_to_closed_form(n_layers, d, seq, ew_tail):
    w = lower_graph(_chain_graph(n_layers, d, seq, tuple(ew_tail)))
    assert w.total_macs() == n_layers * d * d * seq
    assert w.total_weights() == n_layers * d * d
    assert sorted(w.topo_order()) == sorted(w.nodes)
    # folding preserves the elementwise volume exactly
    unfolded = lower_graph(_chain_graph(n_layers, d, seq, tuple(ew_tail)),
                           fold=False)
    assert (sum(n.elements for n in w.other_ops())
            == sum(n.elements for n in unfolded.other_ops())
            == n_layers * len(ew_tail) * seq * d)
    assert len(w.other_ops()) <= len(unfolded.other_ops())


@given(n_layers=st.integers(1, 3), d=st.integers(4, 32),
       seq=st.integers(1, 8),
       ew_tail=st.lists(st.sampled_from(_EW_PRIMS), max_size=2))
@settings(max_examples=12, deadline=None)
def test_random_chain_simulates_under_every_policy(n_layers, d, seq, ew_tail):
    arch = usecase_arch(4)
    mapping = default_mapping(arch, "spatial")
    for pol in POLICIES:
        w = lower_graph(_chain_graph(n_layers, d, seq, tuple(ew_tail)))
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            rep = simulate(arch, w, mapping, schedule=SchedulePolicy(pol))
        assert rep.latency_cycles >= 0
        assert rep.total_energy_uj >= 0
        for oc in rep.op_costs:
            assert oc.latency_cycles >= 0 and oc.macs >= 0
