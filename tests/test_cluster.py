"""Multi-replica control plane: affinity routing, failover, liveness.

The router's contract (docs/SERVING.md "The router"): every request a
live fleet can serve IS served — golden-identical to a direct
``generate()`` call — whatever single-replica event happens under it
(connect refusal, mid-stream death, shed), and the router itself sheds
(503 + Retry-After) only when no live replica could take the request.
Placement is prefix-affine: requests sharing a cached prefix co-locate
on one replica, learned router-side from routing decisions alone.
Every failover path is forced deterministically via the
``router.connect`` / ``router.stream`` / ``router.heartbeat`` fault
points; replicas are real ``ServingFrontDoor``s behind the real HTTP
surface, all in-process.
"""

import http.client
import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu import observability as obs
from znicz_tpu.cluster import (
    STATE_DEAD,
    STATE_DEGRADED,
    STATE_HEALTHY,
    PrefixAffinityIndex,
    ReplicaRegistry,
    ServingRouter,
    build_router_server,
)
from znicz_tpu.core import prng
from znicz_tpu.observability.aggregate import MetricsAggregator
from znicz_tpu.services import PagedDecodeEngine, ServingFrontDoor
from znicz_tpu.services import serve as serve_mod
from znicz_tpu.services.engine import prefix_block_keys
from znicz_tpu.utils import faults
from znicz_tpu.workflow import generate as G
from znicz_tpu.workflow.transformer import init_lm_params

EOS = 14
HEADS = 4
T_MAX = 64
BS = 8  # paged block size == router key block size


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def params():
    prng.seed_all(27)
    return init_lm_params(17, 32, 2, HEADS, max_seq=T_MAX)


def _engine_kwargs(**kw):
    kw.setdefault("n_heads", HEADS)
    kw.setdefault("eos_id", EOS)
    kw.setdefault("batch_size", 2)
    kw.setdefault("block_size", BS)
    kw.setdefault("max_seq", T_MAX)
    kw.setdefault("admit_every", 4)
    return kw


@pytest.fixture(scope="module", autouse=True)
def _warm(params):
    """Compile every program the cluster scenarios will run (prefill,
    decode-window rungs up to the longest request below) ONCE, so the
    zero-new-compiles assertion and the timing-sensitive failover
    tests never eat a first-compile stall."""
    eng = PagedDecodeEngine(params, **_engine_kwargs())
    gen = np.random.default_rng(3)
    # a long request walks the x2 window ladder through every rung the
    # tests can reach; short ones cover admission-at-rung-1
    eng.submit(gen.integers(0, 17, (21,)).astype(np.int32), 30)
    eng.submit(gen.integers(0, 17, (5,)).astype(np.int32), 8)
    eng.run()
    return dict(eng.compile_stats()["programs"])


def _reference(params, prompt, budget, eos=EOS):
    out = np.asarray(
        G.generate(
            params, jnp.asarray(prompt)[None], n_heads=HEADS,
            max_new_tokens=budget, eos_id=eos,
        )
    )[0]
    new = out[len(prompt):]
    hit = np.where(new == eos)[0]
    if len(hit):
        new = new[: hit[0] + 1]
    return [int(t) for t in new]


def _wait_until(cond, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


class _Fleet:
    """N in-process replicas (front door + HTTP server) behind one
    router server — built and torn down per test."""

    def __init__(self, params, n=2, router_kw=None, door_kw=None):
        self.doors, self.srvs = [], []
        for _ in range(n):
            door = ServingFrontDoor(
                lambda: PagedDecodeEngine(params, **_engine_kwargs()),
                max_pending=8,
                **(door_kw or {}),
            )
            srv = serve_mod.build_server(
                directory=".", port=0, frontdoor=door
            )
            threading.Thread(target=srv.serve_forever, daemon=True).start()
            self.doors.append(door)
            self.srvs.append(srv)
        kw = {"block_size": BS, "heartbeat_interval_s": 60.0}
        kw.update(router_kw or {})
        self.router = ServingRouter(**kw)
        for i, srv in enumerate(self.srvs):
            self.router.register(f"rep-{i}", self.url(i))
        self.rsrv = build_router_server(self.router, port=0)
        threading.Thread(
            target=self.rsrv.serve_forever, daemon=True
        ).start()
        self.port = self.rsrv.server_address[1]

    def url(self, i):
        return f"http://127.0.0.1:{self.srvs[i].server_address[1]}"

    def post(self, prompt, max_new=12, timeout=60, port=None):
        conn = http.client.HTTPConnection(
            "127.0.0.1", port or self.port, timeout=timeout
        )
        try:
            conn.request(
                "POST", "/generate",
                body=json.dumps(
                    {"prompt": [int(t) for t in prompt],
                     "max_new_tokens": max_new}
                ),
            )
            resp = conn.getresponse()
            if resp.status != 200:
                return {
                    "status": resp.status,
                    "body": json.loads(resp.read() or b"{}"),
                    "retry_after": resp.getheader("Retry-After"),
                }
            out = {
                "status": 200,
                "tokens": [],
                "done": None,
                "replica_header": resp.getheader("X-Znicz-Replica"),
                "trace_header": resp.getheader("X-Znicz-Trace-Id"),
            }
            while True:
                line = resp.readline()
                if not line:
                    break
                rec = json.loads(line)
                if "token" in rec:
                    out["tokens"].append(rec["token"])
                elif rec.get("done"):
                    out["done"] = rec
            return out
        finally:
            conn.close()

    def close(self):
        for srv in self.srvs:
            srv.shutdown()
            srv.server_close()
        self.rsrv.shutdown()
        self.rsrv.server_close()
        for door in self.doors:
            door.close(grace_s=10.0)
        self.router.close()


@pytest.fixture
def fleet(params):
    f = _Fleet(params)
    yield f
    f.close()


def _counter_value(name, **labels):
    metric = obs.counter(name, "", tuple(labels))
    return (metric.labels(**labels) if labels else metric).value


# -- unit: the affinity index ----------------------------------------------


class TestAffinityIndex:
    def test_learn_overlap_prefix_semantics(self):
        idx = PrefixAffinityIndex()
        idx.learn("a", ["k1", "k2", "k3"])
        assert idx.overlap("a", ["k1", "k2", "k3"]) == 3
        # chain semantics: a missing lead key means NO overlap even if
        # later keys are known
        assert idx.overlap("a", ["kX", "k2"]) == 0
        assert idx.overlap("a", ["k1", "kX", "k3"]) == 1
        assert idx.overlap("b", ["k1"]) == 0

    def test_ttl_decay(self):
        idx = PrefixAffinityIndex(ttl_s=0.05)
        idx.learn("a", ["k1", "k2"])
        assert idx.overlap("a", ["k1", "k2"]) == 2
        time.sleep(0.08)
        assert idx.overlap("a", ["k1", "k2"]) == 0
        assert idx.prune() >= 0  # idempotent after the lookup dropped

    def test_capacity_lru_eviction(self):
        idx = PrefixAffinityIndex(max_keys_per_replica=3)
        idx.learn("a", ["k1", "k2", "k3"])
        idx.learn("a", ["k4"])  # evicts k1 (LRU)
        assert idx.overlap("a", ["k1"]) == 0
        assert idx.overlap("a", ["k4"]) == 1
        # re-touch moves to MRU: k2 survives the next insertion
        idx.learn("a", ["k2"])
        idx.learn("a", ["k5"])
        assert idx.overlap("a", ["k2"]) == 1
        assert idx.overlap("a", ["k3"]) == 0

    def test_drop_replica(self):
        idx = PrefixAffinityIndex()
        idx.learn("a", ["k1", "k2"])
        assert idx.drop("a") == 2
        assert idx.overlap("a", ["k1"]) == 0
        assert idx.drop("a") == 0


# -- unit: the faults after= field -----------------------------------------


class TestFaultsAfter:
    def test_after_skips_then_fires(self):
        faults.inject("t.after", after=2, times=1)
        fired = []
        for _ in range(5):
            try:
                faults.fire("t.after")
                fired.append(False)
            except faults.FaultInjected:
                fired.append(True)
        assert fired == [False, False, True, False, False]

    def test_env_spec_parses_after(self):
        faults._parse_env("t.env:after=1:times=1")
        assert faults.armed("t.env")
        faults.fire("t.env")  # pass-through
        with pytest.raises(faults.FaultInjected):
            faults.fire("t.env")
        assert not faults.armed("t.env")


# -- unit: prefix probe (the engine-privates firewall) ---------------------


class TestPrefixProbe:
    def test_paged_probe_matches_public_keys_and_cache(self, params):
        eng = PagedDecodeEngine(params, **_engine_kwargs())
        gen = np.random.default_rng(11)
        prompt = gen.integers(0, 17, (20,)).astype(np.int32)
        probe = eng.prefix_probe(prompt)
        assert probe["prefix_cache"] is True
        assert probe["block_size"] == BS
        assert probe["block_keys"] == prefix_block_keys(prompt, BS)
        assert len(probe["block_keys"]) == 20 // BS
        assert probe["cached_blocks"] == 0
        # serve it: retirement publishes the full prompt blocks
        eng.submit(prompt, 8)
        eng.run()
        probe2 = eng.prefix_probe(prompt)
        assert probe2["cached_blocks"] == len(probe2["block_keys"])
        assert probe2["cached_tokens"] == probe2["cached_blocks"] * BS
        # a diverging prompt misses from the divergence on
        other = prompt.copy()
        other[2] = (other[2] + 1) % 17
        assert eng.prefix_probe(other)["cached_blocks"] == 0

    def test_probe_without_a_prefix_cache_finds_nothing(self, params):
        # the keys are a pure function of the prompt; with sharing off
        # nothing is ever resident, served or not
        eng = PagedDecodeEngine(
            params, **_engine_kwargs(), prefix_cache=False
        )
        prompt = np.arange(12, dtype=np.int32)
        eng.submit(prompt, 4)
        eng.run()
        probe = eng.prefix_probe(prompt)
        assert probe == {
            "prefix_cache": False, "block_size": BS,
            "block_keys": prefix_block_keys(prompt, BS),
            "cached_blocks": 0, "cached_tokens": 0,
        }

    def test_frontdoor_delegates_and_http_endpoint(self, fleet, params):
        gen = np.random.default_rng(13)
        prompt = gen.integers(0, 17, (16,)).astype(np.int32)
        r = fleet.post(prompt, max_new=6)
        assert r["status"] == 200
        # the replica that served it now reports the cached blocks both
        # via the door hook and over HTTP
        idx = int(r["done"]["router"]["replica"].split("-")[1])
        door_probe = fleet.doors[idx].prefix_probe(prompt)
        assert door_probe["cached_blocks"] == 2
        conn = http.client.HTTPConnection(
            "127.0.0.1", fleet.srvs[idx].server_address[1], timeout=10
        )
        try:
            conn.request(
                "POST", "/prefix_probe",
                body=json.dumps({"prompt": [int(t) for t in prompt]}),
            )
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read()) == door_probe
            # malformed body answers 400, not a dropped connection
            conn.request("POST", "/prefix_probe", body=b"{}")
            resp = conn.getresponse()
            assert resp.status == 400
            resp.read()
        finally:
            conn.close()


# -- the registry state machine --------------------------------------------


class TestRegistry:
    def test_heartbeat_fault_ejects_then_readmits(self, fleet):
        reg = fleet.router.registry
        assert reg.get("rep-0").state == STATE_HEALTHY
        # dead_after consecutive heartbeat timeouts eject
        faults.inject("router.heartbeat", times=2 * reg.dead_after)
        for _ in range(reg.dead_after):
            reg.probe_all()
        assert reg.get("rep-0").state == STATE_DEAD
        assert reg.get("rep-1").state == STATE_DEAD
        assert reg.get("rep-0").ejections == 1
        faults.clear("router.heartbeat")
        # the first answered probe re-admits
        reg.probe_all()
        assert reg.get("rep-0").state == STATE_HEALTHY
        assert reg.get("rep-0").readmissions == 1

    def test_real_server_death_and_rebirth(self, fleet):
        reg = fleet.router.registry
        # seed affinity so the ejection flush is observable
        fleet.router.affinity.learn("rep-0", ["k1", "k2"])
        port = fleet.srvs[0].server_address[1]
        fleet.srvs[0].shutdown()
        fleet.srvs[0].server_close()
        for _ in range(reg.dead_after):
            reg.probe("rep-0")
        assert reg.get("rep-0").state == STATE_DEAD
        # ejection flushed the dead replica's affinity entries
        assert fleet.router.affinity.stats()["keys_per_replica"].get(
            "rep-0", 0
        ) == 0
        # rebirth on the SAME port (allow_reuse_address): one answered
        # probe re-admits without re-registration
        srv = serve_mod.build_server(
            directory=".", port=port, frontdoor=fleet.doors[0]
        )
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        fleet.srvs[0] = srv
        assert reg.probe("rep-0") == STATE_HEALTHY
        r = fleet.post(np.arange(1, 10, dtype=np.int32), max_new=4)
        assert r["status"] == 200

    def test_healthz_carries_load_signal(self, fleet):
        rep = fleet.router.registry.get("rep-0")
        assert rep.health["state"] == "running"
        assert "pending" in rep.health
        assert rep.health["pool_free_frac"] == pytest.approx(1.0)

    def test_degraded_demotion_and_note_success(self, fleet):
        reg = fleet.router.registry
        reg.note_failure("rep-0")
        assert reg.get("rep-0").state == STATE_DEGRADED
        # a streaming 200 heals a transport-blip demotion
        assert reg.note_success("rep-0") == STATE_HEALTHY
        assert reg.get("rep-0").failures == 0

    def test_note_success_does_not_override_self_reported_trouble(
        self, fleet
    ):
        """A replica whose own watchdog reported trouble (probe
        answered, state degraded) stays degraded after a streaming
        200 — serving one stream does not refute 'my watchdog says
        stalled'; only the next probe may promote it."""
        reg = fleet.router.registry
        rep = reg.get("rep-0")
        reg._apply(rep, "degraded", {"state": "stalled"})
        assert rep.state == STATE_DEGRADED and rep.failures == 0
        reg.note_failure("rep-0")  # one transport blip on top
        assert reg.note_success("rep-0") == STATE_DEGRADED
        assert rep.failures == 0
        # replica truth (an answered probe) is what promotes it
        assert reg.probe("rep-0") == STATE_HEALTHY


# -- routing: affinity goldens ---------------------------------------------


class TestRouting:
    def test_shared_prefix_coloc_and_goldens(self, fleet, params):
        gen = np.random.default_rng(5)
        groups = []
        for _ in range(2):
            shared = gen.integers(0, 17, (2 * BS,)).astype(np.int32)
            groups.append(
                [
                    np.concatenate(
                        [shared,
                         gen.integers(0, 17, (5,)).astype(np.int32)]
                    )
                    for _ in range(3)
                ]
            )
        hits0 = _counter_value(
            "znicz_router_affinity_total", signal="hit"
        )
        used = [set(), set()]
        for i in range(3):  # interleave the groups
            for g, prompts in enumerate(groups):
                r = fleet.post(prompts[i])
                assert r["status"] == 200
                assert r["tokens"] == _reference(
                    params, prompts[i], 12
                ), f"group {g} request {i} diverged from generate()"
                assert r["trace_header"]
                assert r["done"]["trace_id"] == r["trace_header"]
                used[g].add(r["done"]["router"]["replica"])
        # each group co-located on ONE replica, and the index said so
        assert all(len(u) == 1 for u in used), used
        assert _counter_value(
            "znicz_router_affinity_total", signal="hit"
        ) - hits0 >= 4  # requests 2..3 of each group routed by overlap
        # the replicas actually HIT their prefix caches (the router's
        # learned index agreed with replica truth)
        total_hits = sum(
            d.engine.stats()["prefix_cache"]["hits"]
            for d in fleet.doors
        )
        assert total_hits >= 4

    def test_least_loaded_spread_without_affinity(self, fleet):
        # distinct prompts (no shared prefix): placement falls back to
        # load and SPREADS across both replicas rather than piling on
        gen = np.random.default_rng(23)
        used = set()
        for _ in range(6):
            prompt = gen.integers(0, 17, (5,)).astype(np.int32)
            r = fleet.post(prompt, max_new=4)
            assert r["status"] == 200
            used.add(r["done"]["router"]["replica"])
            assert r["done"]["router"]["affinity_blocks"] == 0
        assert used == {"rep-0", "rep-1"}

    def test_aggregator_overrides_heartbeat_load(self):
        # pure unit: per-instance aggregator gauges drive the tiebreak
        agg = MetricsAggregator()

        def gauge_fam(value):
            return {
                "znicz_serve_frontdoor_pending": {
                    "type": "gauge", "help": "",
                    "series": [{"labels": {}, "value": value}],
                }
            }

        agg.push("a", gauge_fam(5.0))
        agg.push("b", gauge_fam(1.0))
        assert agg.instance_value(
            "a", "znicz_serve_frontdoor_pending"
        ) == 5.0
        reg = ReplicaRegistry(start=False)
        router = ServingRouter(
            reg, block_size=BS, aggregator=agg
        )
        reg.register("a", "http://127.0.0.1:1", probe=False)
        reg.register("b", "http://127.0.0.1:2", probe=False)
        order = [rep.instance for rep, _ in router.rank([])]
        assert order == ["b", "a"]  # lighter replica first
        router.close()

    def test_slo_burn_rate_demotes_in_the_tiebreak(self):
        """The ROADMAP rung: per-replica /slo burn rates (exported as
        the znicz_serve_slo_burn_rate gauge, pushed per instance) join
        the load tiebreak — a replica burning its error budget ranks
        behind every non-burning peer even when it is otherwise the
        lightest."""
        agg = MetricsAggregator()

        def fam(pending, burn):
            return {
                "znicz_serve_frontdoor_pending": {
                    "type": "gauge", "help": "",
                    "series": [{"labels": {}, "value": pending}],
                },
                "znicz_serve_slo_burn_rate": {
                    "type": "gauge", "help": "",
                    "series": [{"labels": {}, "value": burn}],
                },
            }

        # "a" is idle but BURNING; "b" is busier but healthy
        agg.push("a", fam(pending=0.0, burn=2.5))
        agg.push("b", fam(pending=6.0, burn=0.1))
        reg = ReplicaRegistry(start=False)
        router = ServingRouter(reg, block_size=BS, aggregator=agg)
        reg.register("a", "http://127.0.0.1:1", probe=False)
        reg.register("b", "http://127.0.0.1:2", probe=False)
        order = [rep.instance for rep, _ in router.rank([])]
        assert order == ["b", "a"]  # burn band beats queue depth
        # ...and beats AFFINITY too: the burning replica holds the
        # whole prefix, yet shared-prefix traffic must not keep
        # landing on a breached replica (the band sorts above overlap,
        # like the health band)
        keys = [f"k{i:02d}" for i in range(4)]
        router.affinity.learn("a", keys)
        ranked = router.rank(keys)
        assert [rep.instance for rep, _ in ranked] == ["b", "a"]
        assert dict(
            (rep.instance, ov) for rep, ov in ranked
        )["a"] == 4  # the overlap was seen, the burn band overrode it
        # under the breach threshold affinity rules again
        router.slo_burn_threshold = 5.0
        order = [rep.instance for rep, _ in router.rank(keys)]
        assert order == ["a", "b"]
        router.close()

    def test_frontdoor_publishes_the_burn_gauge(self, fleet, params):
        """The gauge the tiebreak consumes really is written by the
        serving door on its SLO sample cadence."""
        gen = np.random.default_rng(43)
        r = fleet.post(gen.integers(0, 17, (5,)).astype(np.int32),
                       max_new=4)
        assert r["status"] == 200
        fleet.doors[0]._publish_burn()  # engine-thread cadence, forced
        gauge = obs.gauge(
            "znicz_serve_slo_burn_rate",
            "max SLO burn rate across targets and windows with data "
            "(the router load tiebreak's per-instance input)",
        )
        assert gauge.value >= 0.0  # published, readable


# -- failover ---------------------------------------------------------------


class TestFailover:
    def test_connect_refused_fails_over(self, fleet, params):
        gen = np.random.default_rng(31)
        prompt = gen.integers(0, 17, (9,)).astype(np.int32)
        retries0 = _counter_value(
            "znicz_router_retries_total", reason="connect"
        )
        faults.inject("router.connect", times=1)
        r = fleet.post(prompt)
        assert r["status"] == 200
        assert r["tokens"] == _reference(params, prompt, 12)
        assert r["done"]["router"]["retries"] == 1
        assert _counter_value(
            "znicz_router_retries_total", reason="connect"
        ) - retries0 == 1

    def test_midstream_crash_rerouted_golden(self, fleet, params):
        """The acceptance scenario: a replica dies mid-stream after
        tokens were already delivered; the router re-routes to the
        next-best replica, skips the delivered prefix on the resumed
        stream, and the client sees one complete, golden token stream
        — no hang, no duplicate, no gap."""
        gen = np.random.default_rng(37)
        prompt = gen.integers(0, 17, (2 * BS + 3,)).astype(np.int32)
        ref = _reference(params, prompt, 12)
        assert len(ref) >= 4, "need a stream long enough to die inside"
        # 3 records (2 tokens) pass, the next upstream read dies
        faults.inject("router.stream", after=2, times=1)
        r = fleet.post(prompt)
        assert r["status"] == 200
        assert r["tokens"] == ref
        assert r["done"]["router"]["retries"] == 1
        assert r["done"]["finish_reason"] in ("eos", "budget")
        # the abandoned replica's request was cancelled by the dropped
        # connection: its pool sweeps back to fully free
        for door in fleet.doors:
            _wait_until(
                lambda d=door: not d.has_work(),
                what="abandoned request reclaimed",
            )

    def test_all_replicas_crash_typed_error_no_hang(self, fleet):
        """Out of replicas mid-stream: the client still gets a typed
        done record (finish_reason error), never a hang — and the
        router's own ledger counts the request FAILED, not ok."""
        failed0 = _counter_value(
            "znicz_router_requests_total", outcome="failed"
        )
        gen = np.random.default_rng(41)
        prompt = gen.integers(0, 17, (9,)).astype(np.int32)
        # every upstream read attempt dies, on both replicas
        faults.inject("router.stream")
        r = fleet.post(prompt)
        faults.clear("router.stream")
        assert r["status"] == 200  # headers were committed pre-fault
        assert r["done"] is not None
        assert r["done"]["finish_reason"] == "error"
        assert "router" in r["done"]
        assert _counter_value(
            "znicz_router_requests_total", outcome="failed"
        ) - failed0 == 1

    def test_replica_4xx_is_a_client_error_not_failover(self, fleet):
        """A request that passes the router's shallow validation but
        fails replica-side (too large for the KV pool) answers 400 —
        it must not burn a retry, note a failure against the healthy
        replica, or come back as a retryable 503."""
        retries0 = _counter_value(
            "znicz_router_retries_total", reason="connect"
        )
        r = fleet.post(
            np.arange(1, 10, dtype=np.int32), max_new=10_000
        )
        assert r["status"] == 400
        assert "rejected the request" in r["body"]["detail"]
        for rep in fleet.router.registry.replicas():
            assert rep.state == STATE_HEALTHY
            assert rep.failures == 0
        assert _counter_value(
            "znicz_router_retries_total", reason="connect"
        ) - retries0 == 0

    def test_fleet_saturation_503_retry_after(self, fleet):
        """503 + Retry-After ONLY when every live replica shed: park
        both engines in an injected slow tick, fill both pending
        queues to their admission limit, and watch the router shed
        with reason fleet_saturated."""
        from znicz_tpu.services import RejectedError

        for door in fleet.doors:
            door.max_pending = 1
        faults.inject("frontdoor.slow_tick", delay=0.5)
        time.sleep(0.1)  # both engine threads now inside a sleeping tick
        handles = []
        for door in fleet.doors:
            # fill the pending queue to its watermark; the slow tick
            # keeps it from draining (if a submit slipped through into
            # the engine before the fault took hold, the next one parks)
            for _ in range(3):
                try:
                    handles.append(
                        door.submit(np.arange(1, 6, dtype=np.int32), 4)
                    )
                except RejectedError:
                    break
                if len(door._pending) >= door.max_pending:
                    break
            assert len(door._pending) >= door.max_pending
        r = fleet.post(np.arange(1, 8, dtype=np.int32), max_new=4)
        assert r["status"] == 503
        assert r["body"]["reason"] == "fleet_saturated"
        assert int(r["retry_after"]) >= 1
        faults.clear("frontdoor.slow_tick")
        for h in handles:  # the parked requests complete after disarm
            assert h.result(timeout=30.0).finish_reason in (
                "eos", "budget"
            )

    def test_transport_walk_bounded_by_max_retries(self, params):
        """A partitioned fleet must answer 503 after max_retries + 1
        connect timeouts, not one per registered replica."""
        fleet = _Fleet(params, router_kw={"max_retries": 0})
        try:
            connect0 = _counter_value(
                "znicz_router_retries_total", reason="connect"
            )
            faults.inject("router.connect")  # every connect refused
            r = fleet.post(np.arange(1, 8, dtype=np.int32), max_new=4)
            faults.clear("router.connect")
            assert r["status"] == 503
            assert r["body"]["reason"] == "no_upstream"
            # exactly ONE transport attempt was paid (max_retries=0),
            # though two replicas were registered
            assert _counter_value(
                "znicz_router_retries_total", reason="connect"
            ) - connect0 == 1
        finally:
            fleet.close()

    def test_failed_requests_excluded_from_latency_histogram(
        self, fleet
    ):
        def latency_count():
            snap = obs.get_registry().snapshot()[
                "znicz_router_request_seconds"
            ]
            return sum(s["count"] for s in snap["series"])

        n0 = latency_count()
        faults.inject("router.stream")  # every stream read dies
        r = fleet.post(np.arange(1, 8, dtype=np.int32), max_new=4)
        faults.clear("router.stream")
        assert r["done"]["finish_reason"] == "error"
        # a fast terminal error is not a latency measurement (the
        # PR 7 front-door convention, carried to the router)
        assert latency_count() == n0
        r = fleet.post(np.arange(1, 8, dtype=np.int32), max_new=4)
        assert r["status"] == 200
        assert latency_count() == n0 + 1

    def test_garbage_http_replica_counts_as_heartbeat_failure(self):
        """A port reclaimed by a non-HTTP process (BadStatusLine) must
        count toward ejection, not abort the probe sweep."""
        import socket

        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind(("127.0.0.1", 0))
        sock.listen(4)
        port = sock.getsockname()[1]
        stop = threading.Event()

        def garbage_server():
            sock.settimeout(0.2)
            while not stop.is_set():
                try:
                    conn, _ = sock.accept()
                except socket.timeout:
                    continue
                conn.sendall(b"not http at all\r\n")
                conn.close()

        t = threading.Thread(target=garbage_server, daemon=True)
        t.start()
        try:
            reg = ReplicaRegistry(start=False, dead_after=2)
            rep = reg.register("junk", f"http://127.0.0.1:{port}")
            assert rep.failures == 1  # the registration probe counted
            assert reg.probe("junk") == STATE_DEAD
        finally:
            stop.set()
            t.join(timeout=5.0)
            sock.close()

    def test_no_live_replicas_503(self, params):
        reg = ReplicaRegistry(start=False)
        router = ServingRouter(reg, block_size=BS)
        rsrv = build_router_server(router, port=0)
        threading.Thread(target=rsrv.serve_forever, daemon=True).start()
        port = rsrv.server_address[1]
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", port, timeout=10
            )
            conn.request(
                "POST", "/generate",
                body=json.dumps(
                    {"prompt": [1, 2, 3], "max_new_tokens": 4}
                ),
            )
            resp = conn.getresponse()
            body = json.loads(resp.read())
            assert resp.status == 503
            assert body["reason"] == "no_replicas"
            assert resp.getheader("Retry-After") is not None
            conn.close()
            # router healthz mirrors it
            conn = http.client.HTTPConnection(
                "127.0.0.1", port, timeout=10
            )
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            assert resp.status == 503
            resp.read()
            conn.close()
        finally:
            rsrv.shutdown()
            rsrv.server_close()
            router.close()


    def test_misconfigured_instance_fails_over_and_is_noted(
        self, fleet
    ):
        """A registered URL that answers HTTP but is not a replica
        (here: a metrics aggregator — /healthz 200, /generate 404)
        must fail over to a real replica AND count a failure against
        the bogus entry, not surface as a client 400."""
        from znicz_tpu.observability.aggregate import (
            build_aggregator_server,
        )

        asrv = build_aggregator_server(port=0)
        threading.Thread(target=asrv.serve_forever, daemon=True).start()
        try:
            fleet.router.register(
                "bogus",
                f"http://127.0.0.1:{asrv.server_address[1]}",
            )
            assert (
                fleet.router.registry.get("bogus").state
                == STATE_HEALTHY
            )  # its /healthz answers 200 — only traffic exposes it
            # force the bogus entry to be ranked first via affinity
            prompt = np.arange(1, 2 * BS + 1, dtype=np.int32)
            keys = prefix_block_keys(prompt, BS)
            fleet.router.affinity.learn("bogus", keys)
            r = fleet.post(prompt, max_new=4)
            assert r["status"] == 200
            assert r["done"]["router"]["replica"] != "bogus"
            assert r["done"]["router"]["retries"] == 1
            assert fleet.router.registry.get("bogus").failures == 1
            # QUARANTINE: its 200-answering /healthz washes the state
            # back to healthy every probe (the flip-flop), but the
            # traffic-failure streak survives probes — at dead_after
            # the wash stops working and the entry stays degraded
            reg = fleet.router.registry
            for i in range(reg.dead_after - 1):
                assert reg.probe("bogus") == STATE_HEALTHY  # the wash
                p2 = np.arange(
                    3 + i, 3 + i + 2 * BS, dtype=np.int32
                )
                fleet.router.affinity.learn(
                    "bogus", prefix_block_keys(p2, BS)
                )
                assert fleet.post(p2, max_new=4)["status"] == 200
            assert (
                reg.get("bogus").traffic_failures >= reg.dead_after
            )
            assert reg.probe("bogus") == STATE_DEGRADED
            # real served traffic is what lifts the quarantine
            assert reg.note_success("bogus") is not None
            assert reg.get("bogus").traffic_failures == 0
            assert reg.probe("bogus") == STATE_HEALTHY
        finally:
            asrv.shutdown()
            asrv.server_close()
            fleet.router.registry.deregister("bogus")

    def test_sheds_do_not_consume_the_retry_budget(self, fleet):
        """Shed answers are instant: they count in the REPORTED retry
        tally but leave the max_retries budget for the expensive
        failovers (connect timeouts, mid-stream recomputes), and a
        shed replica stays eligible for a later re-route."""
        from znicz_tpu.cluster.router import RoutedStream

        rs = RoutedStream(
            fleet.router, {"prompt": [1], "max_new_tokens": 4}, []
        )
        rs.retries = 5  # five sheds reported...
        assert rs._budget_used == 0
        assert rs._can_retry()  # ...and the crash budget is untouched
        rs._budget_used = fleet.router.max_retries
        assert not rs._can_retry()
        # end-to-end: a persistently shedding replica is walked
        # through (reported) while the healthy one serves
        fleet.doors[0].max_pending = 1
        faults.inject("frontdoor.slow_tick", delay=0.5)
        time.sleep(0.1)
        parked = []
        from znicz_tpu.services import RejectedError
        for _ in range(3):
            try:
                parked.append(
                    fleet.doors[0].submit(
                        np.arange(1, 6, dtype=np.int32), 4
                    )
                )
            except RejectedError:
                break
            if len(fleet.doors[0]._pending) >= 1:
                break
        prompt = np.arange(2, 2 * BS + 2, dtype=np.int32)
        fleet.router.affinity.learn(
            "rep-0", prefix_block_keys(prompt, BS)
        )  # rank the shedding replica first
        r = fleet.post(prompt, max_new=4)
        assert r["status"] == 200
        assert r["done"]["router"]["replica"] == "rep-1"
        assert r["done"]["router"]["retries"] == 1  # the shed, reported
        faults.clear("frontdoor.slow_tick")
        for h in parked:
            h.result(timeout=30.0)

    def test_done_record_n_new_reconciles_with_streamed_tokens(
        self, fleet
    ):
        """A done record from a failover replica that terminated while
        the skipped prefix was still recomputing (e.g. deadline expiry
        mid-recompute) must not claim fewer tokens than the client
        already received from the first replica."""
        from znicz_tpu.cluster.router import RoutedStream

        rs = RoutedStream(
            fleet.router, {"prompt": [1], "max_new_tokens": 8}, []
        )
        rs._sent = 3  # the first replica delivered 3 tokens
        rec = rs._finish(
            {"done": True, "finish_reason": "deadline_exceeded",
             "n_new": 0}
        )
        assert rec["n_new"] == 3
        # the normal path is a no-op clamp
        rs2 = RoutedStream(
            fleet.router, {"prompt": [1], "max_new_tokens": 8}, []
        )
        rs2._sent = 3
        rec2 = rs2._finish(
            {"done": True, "finish_reason": "budget", "n_new": 3}
        )
        assert rec2["n_new"] == 3

    def test_reroute_forwards_remaining_deadline(self, fleet):
        """A failover attempt carries the REMAINING client budget, not
        a fresh full deadline — each retry must not multiply the
        wall-clock a deadline_s=N request can burn."""
        from znicz_tpu.cluster.router import RoutedStream

        rs = RoutedStream(
            fleet.router,
            {"prompt": [1, 2, 3], "max_new_tokens": 4,
             "deadline_s": 5.0},
            [],
        )
        rs._t0 = time.monotonic() - 3.0  # 3 s already burned
        d = rs.payload_now()["deadline_s"]
        assert 1.8 <= d <= 2.1, d
        rs._t0 = time.monotonic() - 60.0  # budget exhausted
        assert rs.payload_now()["deadline_s"] == pytest.approx(0.001)
        # no deadline: payload passes through untouched
        rs2 = RoutedStream(
            fleet.router, {"prompt": [1], "max_new_tokens": 4}, []
        )
        assert "deadline_s" not in rs2.payload_now()


# -- the HTTP surface -------------------------------------------------------


class TestRouterHTTP:
    def test_bad_request_400(self, fleet):
        for body in (
            b"not json",
            json.dumps({"max_new_tokens": 4}).encode(),
            json.dumps({"prompt": "nope", "max_new_tokens": 4}).encode(),
            # a DIGIT string must not be reinterpreted as [1, 2, 3]
            json.dumps({"prompt": "123", "max_new_tokens": 4}).encode(),
            json.dumps({"prompt": [], "max_new_tokens": 4}).encode(),
        ):
            conn = http.client.HTTPConnection(
                "127.0.0.1", fleet.port, timeout=10
            )
            try:
                conn.request("POST", "/generate", body=body)
                resp = conn.getresponse()
                assert resp.status == 400, body
                resp.read()
            finally:
                conn.close()

    def test_replicas_endpoint(self, fleet):
        conn = http.client.HTTPConnection(
            "127.0.0.1", fleet.port, timeout=10
        )
        try:
            conn.request("GET", "/replicas")
            resp = conn.getresponse()
            assert resp.status == 200
            body = json.loads(resp.read())
        finally:
            conn.close()
        assert body["policy"] == "prefix_affinity"
        assert {r["instance"] for r in body["replicas"]} == {
            "rep-0", "rep-1"
        }
        assert all(
            r["state"] == STATE_HEALTHY for r in body["replicas"]
        )
        assert "keys_per_replica" in body["affinity"]

    def test_round_robin_policy_alternates(self, params):
        fleet = _Fleet(
            params, router_kw={"policy": "round_robin"}
        )
        try:
            gen = np.random.default_rng(43)
            shared = gen.integers(0, 17, (BS,)).astype(np.int32)
            seen = []
            for _ in range(4):
                r = fleet.post(shared, max_new=4)
                assert r["status"] == 200
                seen.append(r["done"]["router"]["replica"])
            # same prompt, yet RR alternates — the baseline the bench
            # compares affinity against
            assert seen[0] != seen[1]
            assert seen[0] == seen[2] and seen[1] == seen[3]
        finally:
            fleet.close()


# -- the compile story ------------------------------------------------------


class TestZeroNewPrograms:
    def test_router_and_replicas_add_zero_programs(
        self, fleet, params, _warm
    ):
        """Two replicas + the router serve a mixed affinity stream and
        compile NOTHING beyond the warm single-engine ladder — pinned
        against each engine's ledger AND the process-wide
        znicz_serve_compiles_total."""
        compiles = obs.counter(
            "znicz_serve_compiles_total", "", ("kind", "bucket")
        )
        total0 = sum(
            child.value for child in compiles.children().values()
        )
        gen = np.random.default_rng(47)
        shared = gen.integers(0, 17, (2 * BS,)).astype(np.int32)
        for i in range(4):
            tail = gen.integers(0, 17, (3 + i,)).astype(np.int32)
            r = fleet.post(np.concatenate([shared, tail]), max_new=8)
            assert r["status"] == 200
        total1 = sum(
            child.value for child in compiles.children().values()
        )
        assert total1 - total0 == 0, (
            "routing across replicas compiled new programs"
        )
        for door in fleet.doors:
            extra = set(
                door.engine.compile_stats()["programs"]
            ) - set(_warm)
            assert not extra, f"unexpected programs: {extra}"
