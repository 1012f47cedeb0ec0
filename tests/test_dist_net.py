"""TCP transport end-to-end: handshake, reconnect/idempotence,
partition-vs-death supervision, and the fault-free parity contract.

The pins, mirroring ``docs/fault_tolerance.md`` ("Network transport &
partitions"):

* a real spawned worker served over :class:`TcpHost` /
  :class:`TcpWorkerLink` round-trips messages with wire timestamps on
  both legs;
* a stale incarnation (a zombie predecessor reconnecting after its
  replacement was registered) is REFUSED at the handshake — split-brain
  safe;
* a fault-free TCP harness run replays bit-identically through
  ``simulate_fast`` — the same acceptance gate the pipe backend has to
  pass (``tests/test_dist_harness.py``);
* the ``partition_heal`` campaign: a partition that heals within the
  round hard-deadline rejoins via open-round replay with ZERO respawns
  burned, and every decode stays exact;
* the ``lossy_network`` campaign: latency + drop/dup/reorder on every
  link, decode still exact.
"""

import time

import numpy as np
import pytest

from repro.core import GilbertElliotSource, make_scheme, simulate_fast
from repro.dist import (
    HarnessConfig,
    NetFaultSpec,
    RespawnPolicy,
    Supervisor,
    TcpHost,
    lossy_network,
    partition_heal,
    run_campaign,
    run_harness,
    start_worker_tcp,
)
from repro.dist.net import NetConnection
from repro.dist.supervisor import PARTITIONED

N = 4
SCALE = 0.01
GE = dict(p_ns=0.15, p_sn=0.5, slow_factor=5.0, jitter=0.05)


def _delays(rounds, seed=7):
    return GilbertElliotSource(n=N, seed=seed, **GE).sample_delays(rounds)


def _echo_worker(conn, setup):
    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            return
        if msg.get("kind") == "stop":
            return
        conn.send({"kind": "result", "echo": msg.get("payload")})


def _wait_recv(link, timeout=10.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        msg = link.try_recv()
        if msg is not None:
            return msg
        time.sleep(0.01)
    raise AssertionError("no message within timeout")


def test_tcp_echo_roundtrip_with_wire_timestamps():
    host = TcpHost()
    link = start_worker_tcp(host, 0, _echo_worker, {})
    try:
        deadline = time.perf_counter() + 10.0
        while link.waitable() is None:
            assert time.perf_counter() < deadline, "worker never connected"
            time.sleep(0.01)
        assert link.send({"kind": "round", "payload": 42})
        msg = _wait_recv(link)
        assert msg["echo"] == 42
        # the delivery attaches the worker->master wire lag from the
        # frame timestamp; it is small but positive on one host
        assert 0 <= msg["_wire_lag"] < 5.0
        # and the worker saw the master's "_sent" stamp (echoed back)
        assert link.peer_alive()
    finally:
        link.stop()
        host.close()


def test_stale_incarnation_refused_at_handshake():
    host = TcpHost()
    link = start_worker_tcp(host, 0, _echo_worker, {}, incarnation=1)
    try:
        deadline = time.perf_counter() + 10.0
        while link.waitable() is None:
            assert time.perf_counter() < deadline
            time.sleep(0.01)
        # a zombie predecessor (incarnation 0 < link's 1) dials in: the
        # host must refuse the socket, and the current link's stream
        # must be unaffected
        with pytest.raises(EOFError):
            zombie = NetConnection(host.addr, 0, incarnation=0,
                                   max_retries=2, backoff_s=0.01)
            # the hello is accepted at the socket level; the refusal is
            # the host closing it — the next recv sees EOF and the
            # bounded reconnect exhausts
            deadline = time.perf_counter() + 5.0
            while time.perf_counter() < deadline:
                zombie.poll(0.05)
                zombie.recv()
        assert host.rejected_stale >= 1
        assert link.send({"kind": "round", "payload": "still mine"})
        assert _wait_recv(link)["echo"] == "still mine"
    finally:
        link.stop()
        host.close()


def test_fault_free_tcp_run_replays_bit_identically():
    J = 5
    delays = _delays(J + 2)
    cfg = HarnessConfig(alpha=8.0, time_scale=SCALE, seed=1,
                        transport="tcp")
    res = run_harness("gc", N, J, delays, params={"s": 1}, config=cfg)
    assert not res.aborted, res.abort_reason
    assert sorted(res.decoded_jobs) == list(range(1, J + 1))
    assert res.decode_max_err < 1e-8
    sim = simulate_fast(make_scheme("gc", N, J, s=1), delays,
                        mu=1.0, alpha=8.0, J=J)
    assert np.array_equal(res.trace_model.pattern, sim.effective_pattern)
    assert np.allclose(res.analytic_round_times, sim.round_times * SCALE)
    # no partitions, no heals, no deaths on a clean wire
    assert res.partitions == 0 and res.heals == 0 and not res.deaths
    # the compute/communication split is populated on both legs
    wc = res.ledger.worker_counters()
    assert all(w > 0 for w in wc["wire_send_s"])
    assert all(w > 0 for w in wc["wire_recv_s"])
    assert "wire_send_s" in res.ledger.summary()


def test_partition_heal_campaign_zero_respawns():
    camp = partition_heal(N, 6, worker=1, at_round=3, heal_s=0.8)
    report = run_campaign(camp, time_scale=SCALE)
    assert report.passed, report.violations
    res = report.result
    assert res.partitions >= 1 and res.heals >= 1
    assert res.respawns == 0          # healed, not respawned
    assert sorted(res.decoded_jobs) == list(range(1, 7))
    assert res.decode_max_err < 1e-6
    kinds = [ev["kind"] for ev in res.events]
    assert "partition" in kinds and "heal" in kinds
    assert "respawn" not in kinds


def test_oneway_partition_heals_too():
    camp = partition_heal(N, 6, worker=2, at_round=2, heal_s=0.6,
                          mode="oneway", name="partition-heal-oneway")
    report = run_campaign(camp, time_scale=SCALE)
    assert report.passed, report.violations
    assert report.result.heals >= 1 and report.result.respawns == 0


def test_lossy_network_campaign_decodes_exactly():
    camp = lossy_network(N, 6)
    report = run_campaign(camp, time_scale=SCALE)
    assert report.passed, report.violations
    res = report.result
    assert sorted(res.decoded_jobs) == list(range(1, 7))
    assert res.decode_max_err < 1e-6


def test_partition_escalates_to_respawn_past_deadline():
    """A partition that NEVER heals must escalate: after
    ``partition_timeout_s`` the worker is killed and takes the normal
    death -> respawn path (a partition is only cheaper than a death
    while healing is still plausible)."""
    J = 4
    delays = _delays(J + 3, seed=11)
    cfg = HarnessConfig(
        alpha=8.0, time_scale=SCALE, seed=1, transport="tcp",
        round_timeout=0.2, partition_timeout_s=0.6,
        respawn_max_attempts=2, respawn_backoff_s=0.05,
        respawn_backoff_max_s=0.2,
        net_faults={1: NetFaultSpec(partition_round=2,
                                    partition_rounds=10**6)},
    )
    res = run_harness("m-sgc", N, J, delays,
                      params={"B": 1, "W": 3, "lam": N}, config=cfg)
    assert not res.aborted, res.abort_reason
    assert sorted(res.decoded_jobs) == list(range(1, J + 1))
    assert res.partitions >= 1
    assert res.respawns >= 1          # escalation burned a respawn
    kinds = [ev["kind"] for ev in res.events]
    assert kinds.index("partition") < kinds.index("death")


def test_supervisor_classifies_unreachable_alive_as_partitioned():
    """Unit-level: mark_dead on a reconnectable link with a live peer
    lands in PARTITIONED without burning a death or a respawn."""
    host = TcpHost()
    sup = Supervisor(
        1, _echo_worker, lambda i: {},
        policy=RespawnPolicy(max_attempts=2, partition_timeout_s=30.0),
        transport="tcp",
    )
    try:
        deadline = time.perf_counter() + 10.0
        while sup.links[0].waitable() is None:
            assert time.perf_counter() < deadline
            time.sleep(0.01)
        sup.mark_dead(0, reason="unit test")
        assert sup.state[0] == PARTITIONED
        assert sup.death_count[0] == 0 and sup.respawns[0] == 0
        assert sup.recoverable(0) and not sup.available(0)
        # any message back heals it
        sup.links[0].send({"kind": "round", "payload": 1})
        deadline = time.perf_counter() + 10.0
        while sup.state[0] == PARTITIONED:
            assert time.perf_counter() < deadline
            sup.pump()
            time.sleep(0.01)
        assert sup.state[0] == "alive"
        assert sup.heal_count[0] == 1
    finally:
        sup.stop()
        host.close()


class TestRestrictedUnpickler:
    """The wire deserializes through ``safe_loads`` only: a frame is a
    trust boundary, and a payload naming any global outside the
    builtins + numpy allowlist must die as :class:`FrameError` before
    any constructor runs (docs/fault_tolerance.md, "Network transport
    & partitions")."""

    def test_protocol_messages_roundtrip(self):
        from repro.dist.net import safe_loads
        import pickle

        msgs = [
            {"kind": "round", "t": 3, "attempt": 0,
             "payload": np.arange(12.0).reshape(3, 4)},
            {"kind": "result", "worker": 1, "grad": np.float64(0.5),
             "mask": np.array([True, False])},
            {"kind": "__hello__", "worker": 0, "incarnation": 2},
            {"kind": "pong", "seq": None, "extras": [1, 2.5, "s", (7,)]},
        ]
        for msg in msgs:
            back = safe_loads(pickle.dumps(msg))
            assert set(back) == set(msg)
            for key, ref in msg.items():
                got = back[key]
                if isinstance(ref, np.ndarray):
                    assert got.dtype == ref.dtype
                    np.testing.assert_array_equal(got, ref)
                elif isinstance(ref, tuple):
                    assert tuple(got) == ref
                else:
                    assert got == ref

    def test_forbidden_global_raises_frameerror(self):
        from repro.dist.net import FrameError, safe_loads
        import pickle

        class Gadget:
            def __reduce__(self):
                import os
                return (os.system, ("true",))

        payload = pickle.dumps({"kind": "round", "x": Gadget()})
        with pytest.raises(FrameError, match="forbidden global"):
            safe_loads(payload)

    def test_arbitrary_class_lookup_raises_frameerror(self):
        from repro.dist.net import FrameError, safe_loads
        import pickle

        payload = pickle.dumps(NetConnection.__new__ and time.sleep)
        with pytest.raises(FrameError, match="forbidden global"):
            safe_loads(payload)

    def test_truncated_payload_raises_frameerror(self):
        from repro.dist.net import FrameError, safe_loads
        import pickle

        payload = pickle.dumps({"kind": "ready", "worker": 3})
        with pytest.raises(FrameError):
            safe_loads(payload[: len(payload) // 2])

    def test_hostile_frame_drops_connection_not_process(self):
        """End-to-end: a well-framed but forbidden payload injected at
        a live host socket must not crash anything — the receiver drops
        the socket and the link reports unreachable, the same state a
        partition produces."""
        import pickle
        import socket as socketlib

        from repro.dist.net import HELLO_KIND, TcpWorkerLink, encode_frame

        host = TcpHost()
        link = TcpWorkerLink(0)
        host.register(link)
        try:
            sock = socketlib.create_connection(host.addr)
            hello = pickle.dumps(
                {"kind": HELLO_KIND, "worker": 0, "incarnation": 0}
            )
            sock.sendall(encode_frame(hello, 1, 0.0))
            deadline = time.perf_counter() + 10.0
            while link.waitable() is None:
                assert time.perf_counter() < deadline
                time.sleep(0.01)

            class Evil:
                def __reduce__(self):
                    import os
                    return (os.system, ("true",))

            sock.sendall(encode_frame(pickle.dumps(Evil()), 2, 0.0))
            deadline = time.perf_counter() + 10.0
            while link.try_recv() is None:
                if link.waitable() is None:   # socket dropped: contained
                    break
                assert time.perf_counter() < deadline
                time.sleep(0.01)
            assert link.waitable() is None
        finally:
            try:
                sock.close()
            except OSError:
                pass
            host.close()


# ---------------------------------------------------------------------------
# one process per chip: spawned workers keep jax on the CPU
# ---------------------------------------------------------------------------


def _pin_probe(conn, setup):
    """Spawned child: was any jax backend up before ``pin_to_cpu``, and
    which platform serves jax after it?"""
    import jax
    from jax._src import xla_bridge

    from repro.dist.worker import pin_to_cpu

    pinned_by_shim = jax.config.jax_platforms
    initialised_before = xla_bridge.backends_are_initialized()
    pin_to_cpu()
    conn.send({"kind": "ready", "pinned_by_shim": pinned_by_shim,
               "initialised_before": initialised_before,
               "platforms": jax.config.jax_platforms,
               "backends": sorted(xla_bridge.backends()),
               "platform": jax.devices()[0].platform})
    try:
        conn.recv()
    except (EOFError, OSError):
        return


@pytest.mark.parametrize("transport", ["pipe", "tcp"])
def test_spawned_worker_pins_jax_to_cpu(monkeypatch, transport):
    """A harness worker models a CPU worker: in a child spawned with no
    ``JAX_PLATFORMS`` in its environment, nothing has brought up a jax
    backend by the time ``pin_to_cpu`` runs, and afterwards jax serves
    the CPU, so a parent holding the chip keeps it.  The TCP spawn shim
    pins before it hands over to the worker target."""
    from repro.dist.transport import start_worker

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    host = None
    if transport == "pipe":
        link = start_worker(0, _pin_probe, None)
    else:
        host = TcpHost()
        link = start_worker_tcp(host, 0, _pin_probe, {})
    try:
        msg = _wait_recv(link, timeout=60.0)
        assert msg["kind"] == "ready"
        assert msg["initialised_before"] is False
        assert msg["platforms"] == "cpu"
        assert msg["backends"] == ["cpu"]
        assert msg["platform"] == "cpu"
        if transport == "tcp":
            assert msg["pinned_by_shim"] == "cpu"
    finally:
        link.stop()
        if host is not None:
            host.close()
