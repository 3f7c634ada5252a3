"""Device/compile telemetry: the ledger behind /debug/programs.

The pins (docs/OBSERVABILITY.md "Device & compile telemetry"): every
TRUE first compile of a serving program lands exactly one ledger entry
with nonzero compile wall time — so the engine-sourced ledger count
moves in lockstep with the engine's own program ledger AND
``znicz_serve_compiles_total`` (the repo's zero-new-compiled-programs
invariant now has a wall-clock/FLOPs/bytes record per program); a
second engine with the same geometry adds nothing; the KV pool's byte
gauges mirror the block gauges; and the ``/debug/programs`` +
``POST /debug/profile`` surfaces answer live.
"""

import http.client
import json
import os
import threading

import numpy as np
import pytest

from znicz_tpu import observability as obs
from znicz_tpu.core import prng
from znicz_tpu.observability import device
from znicz_tpu.services import PagedDecodeEngine, ServingFrontDoor
from znicz_tpu.services import serve as serve_mod
from znicz_tpu.workflow.transformer import init_lm_params

EOS = 11
HEADS = 3
T_MAX = 48


@pytest.fixture(scope="module")
def params():
    # a geometry UNIQUE to this module: its first compiles must happen
    # here, whatever ran earlier in the process
    prng.seed_all(91)
    return init_lm_params(19, 24, 2, HEADS, max_seq=T_MAX)


def _compiles_total() -> float:
    m = obs.counter(
        "znicz_serve_compiles_total",
        "distinct compiled engine programs by kind and bucket",
        ("kind", "bucket"),
    )
    return sum(child.value for child in m.children().values())


class TestProgramLedger:
    def test_engine_first_compiles_land_in_the_ledger(self, params):
        ledger0 = device.program_count(source="engine")
        counter0 = _compiles_total()
        eng = PagedDecodeEngine(
            params, n_heads=HEADS, eos_id=EOS, batch_size=2,
            block_size=8, max_seq=T_MAX, admit_every=4,
        )
        gen = np.random.default_rng(7)
        eng.submit(gen.integers(0, 19, (11,)).astype(np.int32), 10)
        eng.submit(gen.integers(0, 19, (4,)).astype(np.int32), 6)
        eng.run()
        d_ledger = device.program_count(source="engine") - ledger0
        d_counter = _compiles_total() - counter0
        n_engine = eng.compile_stats()["n_programs"]
        # the acceptance identity: device ledger == engine ledger ==
        # znicz_serve_compiles_total, entry for entry
        assert d_ledger == d_counter == n_engine
        fresh = device.programs(source="engine")[-d_ledger:]
        for entry in fresh:
            assert entry["compile_s"] > 0.0, entry
            assert entry["kind"] in ("prefill", "paged_chunk", "cow")
        # cost analysis works on this backend: FLOPs recorded
        assert any(entry["flops"] for entry in fresh)

    def test_same_geometry_second_engine_adds_nothing(self, params):
        eng = PagedDecodeEngine(
            params, n_heads=HEADS, eos_id=EOS, batch_size=2,
            block_size=8, max_seq=T_MAX, admit_every=4,
        )
        ledger0 = device.program_count()
        counter0 = _compiles_total()
        gen = np.random.default_rng(9)
        eng.submit(gen.integers(0, 19, (11,)).astype(np.int32), 10)
        eng.run()
        assert device.program_count() == ledger0
        assert _compiles_total() == counter0

    def test_ledger_snapshot_shape(self):
        snap = device.ledger_snapshot()
        assert snap["count"] == len(snap["programs"])
        assert snap["engine_count"] <= snap["count"]
        assert sum(snap["by_kind"].values()) == snap["count"]
        assert snap["compile_seconds_total"] > 0.0
        assert isinstance(snap["device_memory"], list)


class TestGracefulHelpers:
    def test_cost_and_memory_helpers_never_raise(self):
        class Boom:
            def cost_analysis(self):
                raise RuntimeError("no api")

        assert device.stage_cost(Boom()) is None
        assert device.stage_cost(object()) is None
        assert device.lowered_cost(lambda x: x, (1,), {}) is None

    def test_stage_cost_normalizes_list_and_dict(self):
        class DictStage:
            def cost_analysis(self):
                return {"flops": 10.0, "bytes accessed": 20.0}

        class ListStage:
            def cost_analysis(self):
                return [{"flops": 5.0}]

        assert device.stage_cost(DictStage()) == {
            "flops": 10.0, "bytes_accessed": 20.0
        }
        assert device.stage_cost(ListStage())["flops"] == 5.0

    def test_device_memory_never_raises(self):
        out = device.device_memory()
        assert isinstance(out, list)
        for row in out:
            assert "device" in row and "stats" in row


class TestKvPoolBytes:
    def test_byte_gauges_mirror_the_block_gauges(self, params):
        eng = PagedDecodeEngine(
            params, n_heads=HEADS, eos_id=EOS, batch_size=2,
            block_size=8, n_blocks=9, max_seq=T_MAX, admit_every=4,
        )
        assert eng.block_bytes > 0
        st = eng.stats()
        assert st["block_bytes"] == eng.block_bytes
        assert st["pool_bytes"] == eng.usable_blocks * eng.block_bytes
        blocks = obs.gauge(
            "znicz_serve_kv_pool_blocks", "", ("state",)
        )
        by = obs.gauge("znicz_serve_kv_pool_bytes", "", ("state",))
        for state in ("free", "used", "cached"):
            assert (
                by.labels(state=state).value
                == blocks.labels(state=state).value * eng.block_bytes
            )
        gen = np.random.default_rng(17)
        eng.submit(gen.integers(0, 19, (11,)).astype(np.int32), 8)
        eng.run()
        for state in ("free", "used", "cached"):
            assert (
                by.labels(state=state).value
                == blocks.labels(state=state).value * eng.block_bytes
            )


class TestHttpSurfaces:
    @pytest.fixture
    def server(self, params):
        door = ServingFrontDoor(
            lambda: PagedDecodeEngine(
                params, n_heads=HEADS, eos_id=EOS, batch_size=2,
                block_size=8, max_seq=T_MAX, admit_every=4,
            ),
            max_pending=4,
        )
        srv = serve_mod.build_server(directory=".", port=0, frontdoor=door)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        yield srv
        srv.shutdown()
        srv.server_close()
        door.close(grace_s=10.0)

    def _req(self, port, method, path):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            conn.request(method, path)
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read() or b"{}")
        finally:
            conn.close()

    def test_debug_programs_matches_the_ledger(self, server):
        port = server.server_address[1]
        status, body = self._req(port, "GET", "/debug/programs")
        assert status == 200
        assert body["count"] == device.program_count()
        assert body["engine_count"] == device.program_count("engine")
        # every compiled serving program ledgered with NONZERO compile
        # time — the acceptance wording, verbatim
        assert body["count"] > 0
        for entry in body["programs"]:
            assert entry["compile_s"] > 0.0
        assert body["engine_count"] == int(_compiles_total())

    def test_profile_endpoint_smoke(self, server):
        port = server.server_address[1]
        status, body = self._req(
            port, "POST", "/debug/profile?seconds=0.05"
        )
        assert status == 200, body
        assert body["ok"] is True
        assert os.path.isdir(body["log_dir"])
        # jax wrote an actual capture into the directory
        walked = [
            os.path.join(r, f)
            for r, _, fs in os.walk(body["log_dir"]) for f in fs
        ]
        assert walked, "empty profile capture"

    def test_profile_endpoint_bad_seconds_400(self, server):
        port = server.server_address[1]
        for bad in ("nope", "nan", "inf", "-inf"):
            status, body = self._req(
                port, "POST", f"/debug/profile?seconds={bad}"
            )
            assert status == 400 and body["error"] == "bad_request", (
                bad, status, body,
            )

    def test_profile_drains_body_keepalive_survives(self, server):
        """A POST body on /debug/profile must be drained: HTTP/1.1
        keep-alive reuses the socket, and leftover body bytes would be
        parsed as the next request's start line."""
        port = server.server_address[1]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            body = json.dumps({"client": "sends-a-body"})
            conn.request(
                "POST", "/debug/profile?seconds=0.05", body=body,
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            first = json.loads(resp.read())
            assert resp.status == 200, first
            # SAME connection: the next request must parse cleanly
            conn.request("GET", "/debug/programs")
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["count"] >= 0
        finally:
            conn.close()

    def test_profile_busy_409(self, server):
        port = server.server_address[1]
        with device._PROFILE_LOCK:
            status, body = self._req(
                port, "POST", "/debug/profile?seconds=0.05"
            )
        assert status == 409 and body["error"] == "profile_busy"

    def test_capture_profile_clamps_duration(self):
        assert device.PROFILE_MAX_SECONDS <= 60.0
        with pytest.raises(RuntimeError):
            with device._PROFILE_LOCK:
                device.capture_profile(0.01)
