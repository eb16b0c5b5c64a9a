"""The chaos matrix: every wire-plane fault shape, injected deterministically
(tests/chaoshttp.py) in front of REAL peers, driving the real consumers —
``pull_manifest_to_hbm``, ``PeerSet.fetch_into``, and the restore client.

Contracts proven per fault (reset-at-byte, stall-past-deadline, 503 burst,
truncated body, corrupted payload):

- bytes-exact delivery (numpy equality / store digests);
- bounded wall-clock (small read timeouts + the retry deadline);
- no leaked partial writers (``store.partial_size == 0`` after success,
  poisoned bytes never committed);
- window-level recovery, not per-file redo (``bytes_fetched`` accounting
  plus the shim's Range log showing the resume offset);
- retry/breaker counters visible on the metrics surface.

Dep-light on purpose: warm peers are no-MITM ``ProxyServer`` nodes over a
directly-seeded store (no ``cryptography``), so the fast subset runs in
tier-1 and the CI chaos-smoke job everywhere. The combined full matrix is
``slow``.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import time

import numpy as np
import pytest

from demodel_tpu.config import ProxyConfig
from demodel_tpu.delivery import manifest_key
from demodel_tpu.formats import safetensors as st
from demodel_tpu.proxy import ProxyServer
from demodel_tpu.store import Store
from demodel_tpu.utils import metrics as m
from demodel_tpu.utils.faults import PeerHealth

from .chaoshttp import ChaosPeer, FaultPlan, FaultSpec

MODEL = "org/chaos"
#: (896, 896) f32 ≈ 3.2 MB — big enough that a window spans several
#: 1 MiB client chunks (partial progress is real) and small enough to
#: stay under the 4 MiB native-stream threshold (deterministic requests
#: path under the shim)
SHAPE = (896, 896)


@pytest.fixture(autouse=True)
def _fast_wire(monkeypatch):
    """Fast, deterministic wire knobs + fresh process-wide state."""
    from demodel_tpu.parallel.peer import PeerGossip

    monkeypatch.setenv("DEMODEL_RETRY_BASE_MS", "20")
    monkeypatch.setenv("DEMODEL_RETRY_DEADLINE", "60")
    monkeypatch.setenv("DEMODEL_BREAKER_COOLDOWN", "1")
    # a short keep-alive idle bound instead of the old
    # DEMODEL_PROXY_THREADS=16 pin: the pin only masked the serve-plane
    # defect where an idle session pinned a pool worker for its whole
    # keep-alive lifetime (ROADMAP). The idle timeout is the FIX — idle
    # sessions release their worker within a second, so the default-sized
    # pool serves the shim's forwards without 30 s queue waits even on a
    # 1-CPU CI box
    monkeypatch.setenv("DEMODEL_PROXY_IDLE_TIMEOUT", "1")
    PeerHealth.reset_shared()
    PeerGossip.reset_shared()
    m.HUB.reset()
    yield
    PeerHealth.reset_shared()
    PeerGossip.reset_shared()


def _key(tag: str, i) -> str:
    return hashlib.sha256(f"{tag}:{i}".encode()).hexdigest()[:16]


def _seed_store(store: Store, tag: str, n_shards: int, seed: int):
    """Write an n-shard safetensors model + its manifest record straight
    into a store (what a first-party pull would have persisted) — no
    upstream, no PKI."""
    rng = np.random.default_rng(seed)
    tensors, files = {}, []
    for i in range(n_shards):
        name = f"blocks.{i}.w"
        tensors[name] = rng.standard_normal(SHAPE).astype(np.float32)
        blob = st.serialize({name: tensors[name]})
        key = _key(tag, i)
        digest = store.put(key, blob,
                           {"content-type": "application/octet-stream"})
        files.append({
            "name": f"model-{i + 1:05d}-of-{n_shards:05d}.safetensors",
            "key": key, "size": len(blob), "sha256": digest,
            "media_type": "",
        })
    record = {"name": MODEL, "source": "hf", "files": files}
    store.put(manifest_key("hf", MODEL), json.dumps(record).encode(),
              {"kind": "model-manifest", "model": MODEL, "source": "hf"})
    weight_nbytes = sum(f["size"] for f in files)
    return tensors, files, weight_nbytes


@contextlib.contextmanager
def _warm_node(tmp_path, tag: str, n_shards: int = 3, seed: int = 0):
    """A live no-MITM peer serving the seeded model over /peer/*."""
    cfg = ProxyConfig(
        host="127.0.0.1", port=0, mitm_hosts=[], no_mitm=True,
        cache_dir=tmp_path / f"{tag}-cache",
        data_dir=tmp_path / f"{tag}-data")
    store = Store(cfg.cache_dir / "proxy")
    try:
        seeded = _seed_store(store, tag, n_shards, seed)
    finally:
        store.close()
    node = ProxyServer(cfg, verbose=False)
    node.start()
    try:
        yield node, seeded
    finally:
        node.stop()


def _assert_exact(placed, tensors):
    assert set(placed.arrays) == set(tensors)
    for name, want in tensors.items():
        np.testing.assert_array_equal(np.asarray(placed.arrays[name]), want)


def _retries_total() -> float:
    return sum(v for k, v in m.HUB.snapshot().items()
               if k.startswith("peer_retries_total"))


# ----------------------------------------------- pull_manifest_to_hbm


def test_reset_at_byte_resumes_window_not_file(tmp_path, mesh8):
    """An RST partway through a tensor window on the ONLY peer: the
    window resumes at the received offset on the same peer; total network
    bytes stay ≈ the checkpoint (a per-file redo would re-move the landed
    megabytes and trip the bound)."""
    from demodel_tpu.sink.remote import pull_manifest_to_hbm

    with _warm_node(tmp_path, "rst") as (node, (tensors, files, weight)):
        shard1 = files[1]["key"]
        plan = FaultPlan(
            FaultSpec("reset-at-byte", path=shard1, at_byte=2_500_000,
                      min_body=1 << 20),  # the tensor window, not a header
            seed=11)
        with ChaosPeer(node.url, plan) as chaos:
            t0 = time.monotonic()
            report, placed = pull_manifest_to_hbm(MODEL, [chaos.url],
                                                  mesh=mesh8)
            elapsed = time.monotonic() - t0
    assert plan.fired("reset-at-byte") == 1, "the fault never fired"
    _assert_exact(placed, tensors)
    # kept bytes count once, the re-issued remainder once: ≈ checkpoint.
    # (A file-level redo re-fetches the ~2 MB that already landed.)
    assert weight <= report["network_bytes"] <= weight * 1.05 + (1 << 20), \
        f"fetched {report['network_bytes']} of {weight}: window recovery " \
        "degenerated into a redo"
    assert _retries_total() >= 1
    assert elapsed < 60, f"unbounded recovery: {elapsed:.1f}s"


def test_truncated_body_resumes_at_exact_offset(tmp_path, mesh8):
    """A clean-FIN short body: the client must detect the truncation
    (never accept a short window) and the resume Range must start at the
    received offset — proven from the shim's own request log."""
    from demodel_tpu.sink.remote import pull_manifest_to_hbm

    with _warm_node(tmp_path, "trunc") as (node, (tensors, files, weight)):
        shard0 = files[0]["key"]
        cut = 2_400_000
        plan = FaultPlan(
            FaultSpec("truncate", path=shard0, at_byte=cut,
                      min_body=1 << 20), seed=5)
        with ChaosPeer(node.url, plan) as chaos:
            report, placed = pull_manifest_to_hbm(MODEL, [chaos.url],
                                                  mesh=mesh8)
            starts = [int(rng.split("=")[1].split("-")[0])
                      for path, rng in chaos.requests_log
                      if shard0 in path and rng.startswith("bytes=")]
    assert plan.fired("truncate") == 1
    _assert_exact(placed, tensors)
    # requests for the faulted object: header reads (≤ 8), ONE full
    # tensor-window issue, and ONE resume at the kept-chunk boundary —
    # FIN delivery is reliable, so every full client chunk up to the cut
    # survived and the resume starts ≥ 2 MiB into the window
    win_starts = sorted(s for s in starts if s > 8)
    assert win_starts, f"no tensor-window requests logged: {starts}"
    full_start = win_starts[0]
    assert win_starts.count(full_start) == 1, \
        f"the window was re-issued from its start, not resumed: {win_starts}"
    resumes = [s for s in win_starts if s >= full_start + (2 << 20)]
    assert len(resumes) == 1, \
        f"expected exactly one mid-window resume: {win_starts}"
    assert weight <= report["network_bytes"] <= weight * 1.05 + (1 << 20)


def test_503_burst_is_retried_through(tmp_path, mesh8):
    """Two 503s in a row on one object (the bounded-pool overflow shape)
    are absorbed by backoff on the same peer — no failover target needed,
    breaker stays closed (2 < threshold)."""
    from demodel_tpu.sink.remote import pull_manifest_to_hbm

    with _warm_node(tmp_path, "burst") as (node, (tensors, files, weight)):
        plan = FaultPlan(
            FaultSpec("503-burst", path=files[2]["key"], times=2), seed=3)
        with ChaosPeer(node.url, plan) as chaos:
            report, placed = pull_manifest_to_hbm(MODEL, [chaos.url],
                                                  mesh=mesh8)
            assert PeerHealth.shared().allow(chaos.url), \
                "a survivable burst must not open the breaker"
    assert plan.fired("503-burst") == 2
    _assert_exact(placed, tensors)
    assert _retries_total() >= 2
    # the scrape surface carries the retry counters (labeled per peer)
    scrape = m.render()
    assert "# TYPE demodel_peer_retries_total counter" in scrape
    assert 'peer_retries_total{peer="' in scrape


def test_stall_past_deadline_fails_over(tmp_path, mesh8, monkeypatch):
    """A peer that accepts and then sits on the request (hung, but still
    accepting) costs one read-timeout, then the window fails over to the
    healthy twin — bounded wall-clock, bytes exact."""
    from demodel_tpu.sink.remote import pull_manifest_to_hbm

    monkeypatch.setenv("DEMODEL_PEER_TIMEOUT", "2")
    with _warm_node(tmp_path, "stall-a") as (node_a, (tensors, files, weight)):
        cfg_b = ProxyConfig(
            host="127.0.0.1", port=0, mitm_hosts=[], no_mitm=True,
            cache_dir=tmp_path / "stall-b-cache",
            data_dir=tmp_path / "stall-b-data")
        store_b = Store(cfg_b.cache_dir / "proxy")
        try:
            _seed_store(store_b, "stall-a", len(files), 0)  # same content
        finally:
            store_b.close()
        # one shared plan on BOTH rotation members (the consistent-hash
        # striping decides which peer is file 0's primary): the stall
        # fires on whichever shim serves it, and the failover target —
        # the other shim — serves clean (times=1 exhausted)
        plan = FaultPlan(
            FaultSpec("stall", path=files[0]["key"], stall_secs=6.0),
            seed=1)
        with ProxyServer(cfg_b, verbose=False) as node_b, \
                ChaosPeer(node_a.url, plan) as chaos_a, \
                ChaosPeer(node_b.url, plan) as chaos_b:
            t0 = time.monotonic()
            report, placed = pull_manifest_to_hbm(
                MODEL, [chaos_a.url, chaos_b.url], mesh=mesh8)
            elapsed = time.monotonic() - t0
    assert plan.fired("stall") == 1
    _assert_exact(placed, tensors)
    assert elapsed < 30, f"stall was not bounded by the read deadline " \
        f"({elapsed:.1f}s)"
    assert _retries_total() >= 1


def test_corrupt_manifest_fails_over_to_clean_peer(tmp_path, mesh8):
    """A corrupted manifest body (bit flip in the JSON) is junk-content,
    not a wire fault: no retry against the same copy, discovery moves to
    the next peer, delivery stays bytes-exact."""
    from demodel_tpu.sink.remote import pull_manifest_to_hbm

    mkey = manifest_key("hf", MODEL)
    with _warm_node(tmp_path, "cm") as (node, (tensors, files, weight)):
        node_url = node.url  # the native handle dies with the `with`
        plan = FaultPlan(FaultSpec("corrupt", path=mkey, at_byte=0), seed=2)
        with ChaosPeer(node_url, plan) as chaos:
            report, placed = pull_manifest_to_hbm(
                MODEL, [chaos.url, node_url], mesh=mesh8)
    assert plan.fired("corrupt") == 1
    assert report["peer"] == node_url, "discovery kept the poisoned copy"
    _assert_exact(placed, tensors)


def test_corrupt_header_fails_over_to_clean_peer(tmp_path, mesh8):
    """A flipped byte in a safetensors length prefix parses as garbage —
    the header read fails over to the clean peer instead of crashing the
    pull (regression for the ValueError escape in _reader_and_index)."""
    from demodel_tpu.sink.remote import pull_manifest_to_hbm

    with _warm_node(tmp_path, "ch") as (node, (tensors, files, weight)):
        # one shared plan on BOTH rotation members: the consistent-hash
        # striping decides which peer serves file 0's header first, so
        # the corruption rides whichever shim that is, and the failover
        # target (the other shim) serves clean — ring-order-agnostic
        plan = FaultPlan(
            FaultSpec("corrupt", path=files[0]["key"], at_byte=0), seed=4)
        with ChaosPeer(node.url, plan) as chaos_a, \
                ChaosPeer(node.url, plan) as chaos_b:
            report, placed = pull_manifest_to_hbm(
                MODEL, [chaos_a.url, chaos_b.url], mesh=mesh8)
    assert plan.fired("corrupt") == 1
    _assert_exact(placed, tensors)


# --------------------------------------------------- PeerSet.fetch_into


def _peerset_rig(tmp_path, tag, plan, monkeypatch):
    """(chaos_url, dest_store, key, body, digest) around a warm node.
    The native data-plane fetch is pinned off: the shim injects at the
    Python requests layer, and a C++ fallback succeeding first would
    dodge the fault entirely."""
    from demodel_tpu.parallel import peer as peer_mod

    monkeypatch.setattr(peer_mod.PeerSet, "_native_fetch",
                        lambda *a, **k: False)
    cfg = ProxyConfig(
        host="127.0.0.1", port=0, mitm_hosts=[], no_mitm=True,
        cache_dir=tmp_path / f"{tag}-cache",
        data_dir=tmp_path / f"{tag}-data")
    rng = np.random.default_rng(9)
    body = rng.bytes(3_500_000)
    key = _key(tag, "obj")
    store = Store(cfg.cache_dir / "proxy")
    try:
        digest = store.put(key, body,
                           {"content-type": "application/octet-stream"})
    finally:
        store.close()
    node = ProxyServer(cfg, verbose=False)
    node.start()
    chaos = ChaosPeer(node.url, plan)
    return node, chaos, key, body, digest


@pytest.mark.parametrize("kind, times", [
    ("reset-at-byte", 1),
    ("truncate", 1),
    ("503-burst", 2),
])
def test_fetch_into_recovers_from_transport_faults(tmp_path, monkeypatch,
                                                   kind, times):
    """fetch_into under each transport fault: one call delivers the exact
    bytes (digest-verified commit), resuming the kept partial mid-stream,
    and leaves no partial behind."""
    from demodel_tpu.parallel.peer import PeerSet

    plan = FaultPlan(
        FaultSpec(kind, path="/peer/object/", times=times,
                  at_byte=2_000_000), seed=7)
    node, chaos, key, body, digest = _peerset_rig(
        tmp_path, f"fi-{kind}", plan, monkeypatch)
    dest = Store(tmp_path / f"dest-{kind}")
    try:
        ps = PeerSet([chaos.url], timeout=5)
        t0 = time.monotonic()
        assert ps.fetch_into(dest, key, expected_digest=digest) is True
        assert time.monotonic() - t0 < 60
        assert plan.exhausted(), "planned faults never fired"
        assert dest.get(key) == body
        assert dest.partial_size(key) == 0, "leaked partial after success"
        assert dest.meta(key).get("sha256") == digest
        assert _retries_total() >= 1
    finally:
        dest.close()
        chaos.close()
        node.stop()


def test_fetch_into_corrupt_payload_never_commits_poison(tmp_path,
                                                         monkeypatch):
    """Corruption is NOT retried (the wire worked; the bytes are wrong):
    the call degrades to False with nothing committed and nothing
    leaked — and the next call, against the healed peer, delivers
    digest-verified bytes."""
    from demodel_tpu.parallel.peer import PeerSet

    plan = FaultPlan(
        FaultSpec("corrupt", path="/peer/object/", at_byte=1_000_000),
        seed=8)
    node, chaos, key, body, digest = _peerset_rig(
        tmp_path, "fi-corrupt", plan, monkeypatch)
    dest = Store(tmp_path / "dest-corrupt")
    try:
        ps = PeerSet([chaos.url], timeout=5)
        assert ps.fetch_into(dest, key, expected_digest=digest) is False
        assert plan.fired("corrupt") == 1
        assert not dest.has(key), "poisoned bytes were committed"
        assert dest.partial_size(key) == 0, \
            "poisoned partial left for a future resume to build on"
        # healed peer → clean delivery
        assert ps.fetch_into(dest, key, expected_digest=digest) is True
        assert dest.get(key) == body
    finally:
        dest.close()
        chaos.close()
        node.stop()


# ------------------------------------------------------- restore client


def test_restore_survives_mid_tensor_reset(tmp_path, mesh8):
    """The restore plane rides the same reader: an RST inside a tensor
    Range resumes at the received offset against the only endpoint."""
    from demodel_tpu.restore.client import restore
    from demodel_tpu.restore.server import RestoreRegistry, RestoreServer

    rng = np.random.default_rng(13)
    tensors = {"layer.0.w": rng.standard_normal(SHAPE).astype(np.float32),
               "layer.0.b": rng.standard_normal((64,)).astype(np.float32)}
    blob = st.serialize(tensors)
    key = _key("restore", 0)
    store = Store(tmp_path / "restore-store")
    try:
        store.put(key, blob, {"content-type": "application/octet-stream"})
        registry = RestoreRegistry(store)
        assert registry.register_safetensors(MODEL, [key]) == len(tensors)
        plan = FaultPlan(
            FaultSpec("reset-at-byte", path="/tensor/", at_byte=1_500_000),
            seed=6)
        with RestoreServer(registry, host="127.0.0.1") as srv, \
                ChaosPeer(f"http://127.0.0.1:{srv.port}", plan) as chaos:
            t0 = time.monotonic()
            result = restore(chaos.url, MODEL, mesh=mesh8, timeout=10)
            elapsed = time.monotonic() - t0
    finally:
        store.close()
    assert plan.fired("reset-at-byte") == 1
    _assert_exact(result, tensors)
    assert elapsed < 60
    assert _retries_total() >= 1


# -------------------------------------------------------- swarm chaos


def test_swarm_pull_survives_peer_death_and_reset(tmp_path, mesh8,
                                                  monkeypatch):
    """The pod-scale swarm contract under chaos: a 3-host swarm pull with
    (a) an RST mid-chunk on the origin link (window recovery inside the
    chunk fetch) and (b) one swarm host dying the moment a sibling first
    fetches a chunk from it (the ``die`` fault). Must hold: bytes-exact
    delivery on the pulling host, aggregate origin traffic ≈ manifest
    size + only the dead host's re-owned chunks (never a wholesale
    re-pull), and the re-own count visible on the metrics scrape."""
    import threading

    from demodel_tpu.restore.server import RestoreRegistry, RestoreServer
    from demodel_tpu.sink.remote import (
        PeerBlobReader,
        SwarmScheduler,
        pull_manifest_to_hbm,
    )

    monkeypatch.setenv("DEMODEL_SWARM_CHUNK_MB", "1")
    monkeypatch.setenv("DEMODEL_SWARM_GOSSIP_MS", "150")
    monkeypatch.setenv("DEMODEL_SWARM_FILL_TIMEOUT", "4")
    chunk = 1 << 20
    with _warm_node(tmp_path, "swarm") as (node, (tensors, files, weight)):
        plan = FaultPlan(
            FaultSpec("reset-at-byte", path=files[1]["key"],
                      at_byte=600_000, min_body=1 << 20),
            seed=17)
        die_plan = FaultPlan(FaultSpec("die", path="/chunk/"), seed=18)
        servers, stores, scheds = [], [], {}
        chaos_c = None
        with ChaosPeer(node.url, plan) as origin:
            try:
                urls = {}
                for hid in ("hA", "hB", "hC"):
                    st = Store(tmp_path / f"swarm-{hid}")
                    srv = RestoreServer(RestoreRegistry(st),
                                        host="127.0.0.1").start()
                    stores.append(st)
                    servers.append(srv)
                    urls[hid] = f"http://127.0.0.1:{srv.port}"
                # hC's serve surface dies (RST + permanently dark) the
                # first time a sibling pulls a chunk off it — i.e. right
                # AFTER it advertised possession: the sharpest mid-pull
                # death shape for the succession logic
                chaos_c = ChaosPeer(urls["hC"], die_plan)
                participants = {"hA": urls["hA"], "hB": urls["hB"],
                                "hC": chaos_c.url}
                for hid in participants:
                    scheds[hid] = SwarmScheduler("chaos-swarm", hid,
                                                 participants)
                for hid in ("hB", "hC"):
                    s = scheds[hid]
                    for f in files:
                        s.add_file(f["key"], int(f["size"]),
                                   PeerBlobReader(origin.url, f["key"],
                                                  int(f["size"])))
                    s.start()
                errors: list = []

                def participate(s):
                    try:
                        s.fetch_all()
                    except Exception as e:  # noqa: BLE001 — surfaced below
                        errors.append(e)

                ths = [threading.Thread(target=participate,
                                        args=(scheds[h],), daemon=True)
                       for h in ("hB", "hC")]
                for t in ths:
                    t.start()
                t0 = time.monotonic()
                report, placed = pull_manifest_to_hbm(
                    MODEL, [origin.url], mesh=mesh8, swarm=scheds["hA"])
                elapsed = time.monotonic() - t0
                for t in ths:
                    t.join(timeout=90)
                assert not any(t.is_alive() for t in ths), \
                    "a swarm participant wedged"
                assert errors == []
                owned_c = scheds["hC"].stats()["owned_chunks"]
            finally:
                for s in scheds.values():
                    s.close()
                if chaos_c is not None:
                    chaos_c.close()
                for srv in servers:
                    srv.stop()
                for st in stores:
                    st.close()
    # bytes-exact despite the origin RST and the dead sibling
    _assert_exact(placed, tensors)
    assert plan.fired("reset-at-byte") == 1, "the origin RST never fired"
    assert die_plan.fired("die") == 1, "hC never died"
    assert elapsed < 120, f"unbounded swarm recovery: {elapsed:.1f}s"
    # succession, not wholesale: only hC's unserved chunks re-sourced,
    # each exactly once (the ring successor), proven from the scrape
    refetched = m.HUB.get("swarm_chunks_refetched_total")
    assert 1 <= refetched <= owned_c, \
        f"re-own miscounted: {refetched} of {owned_c} hC-owned chunks"
    origin_chunk_bytes = m.HUB.get("swarm_origin_bytes_total")
    assert weight <= origin_chunk_bytes <= weight + refetched * chunk, \
        f"aggregate origin chunk bytes {origin_chunk_bytes} vs manifest " \
        f"{weight} (+{refetched} re-owned chunks): swarm degenerated " \
        "into per-host origin pulls"
    # wire truth from the shim side: total origin body bytes (chunks +
    # manifest/header reads per host) stay far under the 3× a
    # non-swarm 3-host pull would move
    assert origin.bytes_served <= weight + refetched * chunk + (2 << 20), \
        f"origin served {origin.bytes_served} for a {weight}-byte manifest"
    scrape = m.render()
    assert "# TYPE demodel_swarm_chunks_refetched_total counter" in scrape
    assert "# TYPE demodel_swarm_origin_bytes_total counter" in scrape
    assert "# TYPE demodel_swarm_peer_bytes_total counter" in scrape


# ------------------------------------------------------ the full matrix


@pytest.mark.slow
def test_full_chaos_matrix(tmp_path, mesh8, monkeypatch):
    """Every fault shape at once, on one pull: reset, truncation, a 503
    burst, a corrupted header, and a stall — across a 6-shard checkpoint
    with one chaotic and one healthy peer. Bytes exact, every fault
    fired, wall-clock bounded, accounting sane."""
    from demodel_tpu.sink.remote import pull_manifest_to_hbm

    monkeypatch.setenv("DEMODEL_PEER_TIMEOUT", "2")
    with _warm_node(tmp_path, "mx", n_shards=6, seed=21) as (
            node_a, (tensors, files, weight)):
        cfg_b = ProxyConfig(
            host="127.0.0.1", port=0, mitm_hosts=[], no_mitm=True,
            cache_dir=tmp_path / "mx-b-cache",
            data_dir=tmp_path / "mx-b-data")
        store_b = Store(cfg_b.cache_dir / "proxy")
        try:
            _seed_store(store_b, "mx", len(files), 21)
        finally:
            store_b.close()
        plan = FaultPlan(
            FaultSpec("reset-at-byte", path=files[0]["key"],
                      at_byte=2_500_000, min_body=1 << 20),
            FaultSpec("503-burst", path=files[1]["key"], times=2),
            FaultSpec("truncate", path=files[2]["key"], at_byte=2_000_000,
                      min_body=1 << 20),
            FaultSpec("corrupt", path=files[3]["key"], at_byte=0),
            FaultSpec("stall", path=files[4]["key"], stall_secs=5.0),
            seed=42)
        # BOTH peers are chaotic (one shared plan): files stripe across
        # the two rotations, so every fault fires on whichever shim owns
        # its file's primary — and every failover target is itself a
        # chaos shim. Flaky friends are the steady state here.
        with ProxyServer(cfg_b, verbose=False) as node_b, \
                ChaosPeer(node_a.url, plan) as chaos_a, \
                ChaosPeer(node_b.url, plan) as chaos_b:
            t0 = time.monotonic()
            report, placed = pull_manifest_to_hbm(
                MODEL, [chaos_a.url, chaos_b.url], mesh=mesh8)
            elapsed = time.monotonic() - t0
    _assert_exact(placed, tensors)
    for kind in ("reset-at-byte", "503-burst", "truncate", "corrupt",
                 "stall"):
        assert plan.fired(kind) >= 1, f"{kind} never fired"
    assert elapsed < 120, f"matrix run unbounded: {elapsed:.1f}s"
    # every recovery is window- or file-scoped: the pod never re-pulls
    # the checkpoint (header re-reads + one corrupt-header file redo are
    # the only double-moved bytes)
    assert report["network_bytes"] <= weight * 1.4 + (4 << 20), \
        f"{report['network_bytes']} vs {weight}"
    assert _retries_total() >= 3
    scrape = m.render()
    assert 'peer_retries_total{peer="' in scrape
