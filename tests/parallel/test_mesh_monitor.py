"""MeshMonitor unit tests: the UDP heartbeat ring in one process.

Two (or three) monitors on loopback ports stand in for the per-host
liveness agents. These tests pin the detector contract the recovery
orchestrator builds on: a silent rank is declared LOST only after
``death_timeout_s`` (a late beat is a transient partition and declares
nothing), a lost rank that beats again is REJOINed, and every membership
change bumps the epoch exactly once per observer.
"""

from __future__ import annotations

import socket
import time

import pytest

from vllm_tpu.parallel.mesh_monitor import MeshMonitor, parse_hb_addrs
from vllm_tpu.resilience import failpoints as fp

# Fast ring so loss detection fits in test time while the timeout still
# dwarfs the interval (the constructor enforces that ordering anyway).
INTERVAL = 0.05
TIMEOUT = 0.4


@pytest.fixture(autouse=True)
def _disarm_failpoints():
    fp.deactivate()
    yield
    fp.deactivate()


def free_addrs(n: int) -> list[tuple[str, int]]:
    socks = []
    for _ in range(n):
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    addrs = [s.getsockname() for s in socks]
    for s in socks:
        s.close()
    return addrs


def wait_for(cond, timeout: float = 10.0, msg: str = "condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    pytest.fail(f"timed out waiting for {msg}")


def make_ring(n: int, **kw) -> list[MeshMonitor]:
    addrs = free_addrs(n)
    kw.setdefault("heartbeat_interval_s", INTERVAL)
    kw.setdefault("death_timeout_s", TIMEOUT)
    return [MeshMonitor(r, addrs, **kw) for r in range(n)]


# -- parsing & validation ----------------------------------------------


def test_parse_hb_addrs():
    assert parse_hb_addrs("") == []
    assert parse_hb_addrs("a:1,b:2") == [("a", 1), ("b", 2)]
    # Whitespace and trailing commas tolerated (hand-written env vars).
    assert parse_hb_addrs(" a:1 , b:2 ,") == [("a", 1), ("b", 2)]


@pytest.mark.parametrize("spec", ["nocolon", "host:", ":123", "h:notaport"])
def test_parse_hb_addrs_malformed(spec):
    with pytest.raises(ValueError, match="malformed address"):
        parse_hb_addrs(spec)


def test_constructor_validation():
    addrs = free_addrs(2)
    with pytest.raises(ValueError, match="out of range"):
        MeshMonitor(2, addrs)
    with pytest.raises(ValueError, match="must exceed"):
        MeshMonitor(0, addrs, heartbeat_interval_s=1.0,
                    death_timeout_s=0.5)


def test_single_rank_ring_is_inert():
    (m,) = make_ring(1)
    m.start()  # nothing to monitor; must not spin threads or error
    assert m.status() == {"size": 1, "world_size": 1, "lost_ranks": [],
                          "epoch": 0, "state": "healthy"}
    m.stop()


# -- loss, rejoin, epochs ----------------------------------------------


def test_loss_declared_after_timeout_and_rejoin_on_beat():
    m0, m1 = make_ring(2)
    m0.start()
    m1.start()
    try:
        wait_for(lambda: m0.beats_received > 0 and m1.beats_received > 0,
                 msg="initial beats")
        assert m0.status()["state"] == "healthy"

        # Kill rank 1's agent: rank 0 must classify host death, but not
        # before a full death timeout of silence. The silence runs from
        # the last beat rank 0 HEARD (up to an interval before the stop),
        # so it is read off the monitor's own record, not off a clock
        # started here: that read 0.39 s of 0.4 one run in six.
        m1.stop()
        wait_for(lambda: m0.lost_ranks() == [1], msg="rank 1 LOST")
        events = m0.poll_events()
        assert [(e.kind, e.rank) for e in events] == [("lost", 1)]
        assert events[0].at - m0._last_seen[1] >= TIMEOUT
        st = m0.status()
        assert st["state"] == "degraded"
        assert st["size"] == 1 and st["lost_ranks"] == [1]
        assert st["epoch"] == 1

        # The lost host comes back and announces itself by beating.
        m1b = MeshMonitor(1, m0._addrs, heartbeat_interval_s=INTERVAL,
                          death_timeout_s=TIMEOUT)
        m1b.start()
        try:
            wait_for(lambda: m0.lost_ranks() == [], msg="rank 1 REJOIN")
            events = m0.poll_events()
            assert [(e.kind, e.rank) for e in events] == [("rejoin", 1)]
            st = m0.status()
            assert st["state"] == "healthy" and st["size"] == 2
            assert st["epoch"] == 2
        finally:
            m1b.stop()
    finally:
        m0.stop()
        m1.stop()


def test_loss_propagates_around_three_rank_ring():
    # Rank 1 beats rank 2 and watches rank 0; when rank 1 dies, rank 2
    # detects it directly and rank 0 must learn via the forwarded LOST
    # message (it never watched rank 1 itself).
    m0, m1, m2 = ring = make_ring(3)
    for m in ring:
        m.start()
    try:
        wait_for(lambda: all(m.beats_received > 0 for m in ring),
                 msg="ring warm")
        m1.stop()
        wait_for(lambda: m0.lost_ranks() == [1] and m2.lost_ranks() == [1],
                 msg="both survivors see rank 1 LOST")
        # The survivors close ranks: 2 now beats 0 and 0 beats 2, so the
        # detector keeps full coverage of the shrunken ring.
        before0, before2 = m0.beats_received, m2.beats_received
        wait_for(lambda: m0.beats_received > before0
                 and m2.beats_received > before2,
                 msg="shrunken ring still beating")
        assert m0.status()["size"] == 2
    finally:
        for m in ring:
            m.stop()


# -- failpoints: induced silence vs transient delay ---------------------


def test_heartbeat_drop_failpoint_silences_rank():
    # `mesh.heartbeat=drop` on rank 1 only: the process is alive but
    # mute, which is indistinguishable from host death on the wire.
    fp.configure("mesh.heartbeat=drop@rank=1")
    m0, m1 = make_ring(2)
    m0.start()
    m1.start()
    try:
        wait_for(lambda: m0.lost_ranks() == [1],
                 msg="silenced rank declared LOST")
        # The mute rank still hears rank 0 and never declares it lost.
        assert m1.lost_ranks() == []
    finally:
        m0.stop()
        m1.stop()


def test_heartbeat_delay_under_timeout_declares_nothing():
    # Beats delayed well under the death timeout model a transient
    # partition: the `--mesh-death-timeout-s` classification boundary.
    fp.configure("mesh.heartbeat=delay(0.05)@rank=1")
    m0, m1 = make_ring(2)
    m0.start()
    m1.start()
    try:
        time.sleep(TIMEOUT * 3)
        assert m0.lost_ranks() == []
        assert m0.poll_events() == []
        assert m0.status()["state"] == "healthy"
    finally:
        m0.stop()
        m1.stop()
