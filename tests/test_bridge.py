"""repro.bridge: bridged-vs-standalone token parity, descriptor→field
translation, closed-loop feedback, slot-residency routing, and the
engine↔cluster config-byte accounting identity."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.bridge import (
    ClosedLoopDriver,
    TenantEngine,
    descriptor_fields,
    descriptor_nbytes,
    descriptor_request,
    padded_nbytes,
)
from repro.cluster import Cluster
from repro.configs import get
from repro.core.accelerators import REGISTRY
from repro.models.model import Model
from repro.serving import Request, ServingEngine

OPENGEMM = REGISTRY["opengemm"]
GEMMINI = REGISTRY["gemmini"]


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(get("qwen2-0.5b").reduced(), remat="none")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    decode_fn = ServingEngine.compile_decode(model)
    return model, params, decode_fn


PROMPTS = [[5, 9, 2], [7, 1], [3, 3, 3, 3]]


def _engine(small_model, **kw):
    model, params, decode_fn = small_model
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 64)
    eng = ServingEngine(model, params, decode_fn=decode_fn, **kw)
    for uid, prompt in enumerate(PROMPTS):
        eng.submit(Request(uid=uid, prompt=list(prompt), max_new_tokens=5))
    return eng


def _tokens(finished):
    return {r.uid: list(r.generated) for r in finished}


# -------------------------------------------------------- descriptor fields


def _desc(max_slots=4):
    return {
        "tokens": np.arange(max_slots, dtype=np.int32).reshape(max_slots, 1),
        "positions": np.zeros((max_slots,), np.int32),
        "live_mask": np.array([True] * (max_slots - 1) + [False]),
        "max_len": np.int32(64),
    }


def test_descriptor_fields_price_each_leaf_at_wire_size():
    desc = _desc()
    fields = descriptor_fields(desc, OPENGEMM)
    # int32 leaves on a 4-byte-field device: one field per element; the
    # 4-slot bool mask packs into exactly one field
    assert len(fields) == 4 + 4 + 1 + 1
    assert padded_nbytes(desc, OPENGEMM) == descriptor_nbytes(desc) == 4 * 10
    # 8-byte fields (gemmini) pad the 4-byte leaves
    assert padded_nbytes(desc, GEMMINI) > descriptor_nbytes(desc)


def test_leaf_changes_atomically():
    """All words of a leaf share its digest: any element change re-sends
    the whole leaf (matching the engine executor's whole-leaf comparison),
    and an identical leaf elides entirely."""
    from repro.sched import ConfigStateCache

    cache = ConfigStateCache(bytes_of=lambda n, v: OPENGEMM.bytes_per_field)
    cache.dispatch("t", descriptor_fields(_desc(), OPENGEMM))
    changed = _desc()
    changed["tokens"][2, 0] = 99  # one element of one leaf
    plan = cache.dispatch("t", descriptor_fields(changed, OPENGEMM))
    assert {n.split("#")[0] for n in plan.sent} == {"['tokens']"}
    assert len(plan.sent) == 4  # the whole tokens leaf, not one word
    again = cache.dispatch("t", descriptor_fields(changed, OPENGEMM))
    assert not again.sent


def test_descriptor_request_carries_real_fields():
    req = descriptor_request("t0", _desc(), OPENGEMM, dims=(8, 16, 64),
                             arrival_time=42.0)
    assert req.accel == "opengemm" and req.arrival_time == 42.0
    regs = req.regs_for(OPENGEMM)
    assert (regs["M"], regs["K"], regs["N"]) == (8, 16, 64)
    assert any(name.startswith("['tokens']") for name in regs)


# ------------------------------------------------------------- token parity


def test_bridged_tokens_bit_identical_to_standalone(small_model):
    """ISSUE 4 satellite: the bridge may never perturb model output — a
    cluster-bridged engine generates exactly the tokens the same engine
    produces standalone, for the same seeds and submission order."""
    standalone = _engine(small_model)
    want = _tokens(standalone.run_until_done())

    bridged = _engine(small_model)
    tenant = TenantEngine("t0", bridged, accel="opengemm")
    cluster = Cluster.uniform(2, {"opengemm": 1}, policy="affinity",
                              sticky=True, link="noc")
    ClosedLoopDriver([tenant], cluster).run()
    got = _tokens(bridged.finished)
    assert got == want

    # and the routing policy is irrelevant to output: round-robin too
    rr = _engine(small_model)
    ClosedLoopDriver(
        [TenantEngine("t0", rr, accel="opengemm")],
        Cluster.uniform(2, {"opengemm": 1}, policy="round_robin"),
    ).run()
    assert _tokens(rr.finished) == want


# -------------------------------------------------------------- closed loop


def test_closed_loop_feedback_serializes_a_tenants_steps(small_model):
    """A tenant's next step arrives exactly when its previous step
    completed: queueing delay throttles the token clock (closed loop),
    instead of piling into a percentile (open loop)."""
    eng = _engine(small_model)
    tenant = TenantEngine("t0", eng, accel="opengemm")
    cluster = Cluster.uniform(1, {"opengemm": 1}, policy="affinity",
                              sticky=True, link="noc")
    rep = ClosedLoopDriver([tenant], cluster).run()
    steps = [s for s in rep.steps if s.tenant == "t0"]
    assert len(steps) >= 5
    for prev, nxt in zip(steps, steps[1:]):
        assert nxt.arrival == prev.completion
        assert nxt.completion > nxt.arrival
    # token goodput is finite and accounted on the cluster clock
    assert rep.tokens == sum(s.tokens for s in steps) > 0
    assert rep.tokens_per_kcycle > 0.0
    assert rep.serving["t0"].p99_decode >= rep.serving["t0"].p50_decode > 0.0


def test_sticky_router_binds_decode_to_the_kv_home(small_model):
    """Slot residency is binding: every launch of a bridged tenant lands
    on the host that adopted its KV context, even with other hosts idle."""
    eng = _engine(small_model)
    tenant = TenantEngine("t0", eng, accel="opengemm")
    cluster = Cluster.uniform(3, {"opengemm": 1}, policy="affinity",
                              sticky=True)
    rep = ClosedLoopDriver([tenant], cluster).run()
    placements = rep.cluster.placements()["t0"]
    assert len(placements) == 1  # one home host, all launches
    home = next(iter(placements))
    assert cluster.router.home("t0").id == home


def test_round_robin_without_sticky_shuffles_the_tenant(small_model):
    eng = _engine(small_model)
    tenant = TenantEngine("t0", eng, accel="opengemm")
    cluster = Cluster.uniform(3, {"opengemm": 1}, policy="round_robin",
                              sticky=False)
    rep = ClosedLoopDriver([tenant], cluster).run()
    assert len(rep.cluster.placements()["t0"]) == 3  # thrashes every host


# ------------------------------------------------------- accounting parity


def test_config_bytes_match_engine_accounting(small_model):
    """The cluster device's field-granular cache and the engine executor's
    leaf-granular cache are independent implementations fed one stream:
    under sticky routing their byte accounting must agree exactly (modulo
    the documented launch-command and tile-register terms)."""
    engines = [_engine(small_model) for _ in range(2)]
    tenants = [TenantEngine(f"t{i}", e, accel="opengemm")
               for i, e in enumerate(engines)]
    cluster = Cluster.uniform(2, {"opengemm": 1}, policy="affinity",
                              sticky=True, link="noc")
    rep = ClosedLoopDriver(tenants, cluster).run()
    parity = rep.config_parity()
    assert set(parity) == {"t0", "t1"}
    for tenant, p in parity.items():
        assert p["matched"], (tenant, p)
        # elision is real: resident state kept most descriptor bytes off
        # the wire after the first step
        assert p["cluster_bytes_elided"] > 0


def test_step_timeline_shows_first_step_full_send(small_model):
    eng = _engine(small_model)
    tenant = TenantEngine("t0", eng, accel="opengemm")
    cluster = Cluster.uniform(1, {"opengemm": 1}, policy="affinity",
                              sticky=True)
    rep = ClosedLoopDriver([tenant], cluster).run()
    timeline = rep.step_timeline("t0")
    assert len(timeline) == rep.serving["t0"].steps
    (_, first_sent, _), (_, later_sent, later_elided) = timeline[0], timeline[-1]
    assert first_sent > later_sent  # cold full send vs steady-state delta
    assert later_elided > 0  # invariant config rode device-resident state
    # the cluster-wide launch timeline carries the same traffic, unfolded
    launches = rep.cluster.descriptor_timeline("t0")
    assert sum(b for _, b, _ in launches) == sum(b for _, b, _ in timeline)


def test_serving_roofline_points_are_config_bound_here(small_model):
    """Tiny decode tiles against per-step descriptor traffic sit left of
    the knee: the bridged serving points land configuration-bound, on the
    same axes as every other roofline point in the repo."""
    eng = _engine(small_model)
    tenant = TenantEngine("t0", eng, accel="opengemm")
    cluster = Cluster.uniform(1, {"opengemm": 1}, policy="affinity",
                              sticky=True, link="noc")
    rep = ClosedLoopDriver([tenant], cluster).run()
    (pt,) = rep.serving_roofline()
    assert pt.name == "serve[t0]"
    assert pt.i_oc > 0 and pt.performance > 0
    assert pt.bound == "configuration"


def test_overlapped_cluster_raises_token_goodput_bit_identically(small_model):
    """ISSUE 5: runtime config overlap threads through closed-loop decode —
    on an overlapped PCIe cluster each descriptor's burst DMA streams
    behind the previous launch's compute, shortening the feedback edge, so
    tokens/kcycle rises while the generated tokens (and the engine↔cluster
    byte-accounting parity) stay exactly the same."""
    def run(overlap):
        engines = [_engine(small_model) for _ in range(2)]
        tenants = [TenantEngine(f"t{i}", e, accel="opengemm")
                   for i, e in enumerate(engines)]
        cluster = Cluster.uniform(1, {"opengemm": 1}, policy="affinity",
                                  sticky=True, link="pcie", overlap=overlap)
        rep = ClosedLoopDriver(tenants, cluster).run()
        tokens = {t.tenant: _tokens(t.engine.finished) for t in tenants}
        return rep, tokens

    ser, ser_tokens = run("serialized")
    ov, ov_tokens = run("overlapped")
    assert ov_tokens == ser_tokens  # timing moved, semantics did not
    assert ov.cluster.makespan < ser.cluster.makespan
    assert ov.tokens_per_kcycle > ser.tokens_per_kcycle
    # the win is exactly the hidden T_set: cycles streamed behind compute
    assert ser.overlap_summary()["hidden_config_cycles"] == 0.0
    assert ov.overlap_summary()["hidden_config_cycles"] > 0.0
    # byte accounting is untouched by overlap — parity still exact
    assert all(p["matched"] for p in ov.config_parity().values())
    assert ov.cluster.bytes_sent == ser.cluster.bytes_sent


def test_bridged_tokens_bit_identical_with_launches_staged_ahead(small_model):
    """Four requests on four slots: the engine stages decode launches
    ahead of their reads, so one step issues two decode launches and the
    last step only reads. The bridge mirrors every launch, perturbs no
    token, keeps its byte accounting exact, and records every token on
    the step that read it."""
    def engine():
        eng = _engine(small_model)
        eng.submit(Request(uid=len(PROMPTS), prompt=[2, 6], max_new_tokens=5))
        return eng

    standalone = engine()
    want = _tokens(standalone.run_until_done())
    assert standalone.ahead.launches > 0

    bridged = engine()
    tenant = TenantEngine("t0", bridged, accel="opengemm")
    cluster = Cluster.uniform(1, {"opengemm": 1}, policy="affinity",
                              sticky=True, link="noc")
    rep = ClosedLoopDriver([tenant], cluster).run()
    assert _tokens(bridged.finished) == want
    assert bridged.ahead.launches == standalone.ahead.launches
    steps = [s for s in rep.steps if s.tenant == "t0"]
    assert [s.launches for s in steps].count(0) == 1  # the last read
    assert rep.tokens == sum(s.tokens for s in steps) == 5 * len(want)
    assert tenant.launches == rep.cluster.launches
    assert all(p["matched"] for p in rep.config_parity().values())
