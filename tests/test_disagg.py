"""Disaggregated prefill/decode serving (serving/disagg.py).

The contract under test (ISSUE 19 acceptance):
- SRV_PAGES frames round-trip under BOTH meta codecs (v2 JSON and the
  negotiated v3 bmeta) with the page payload bit-exact, and a CRC
  bit-flip anywhere in the frame is a typed FrameCorruptError — page
  bytes ride the same framing discipline as every other wire value
- the PrefixCache hash chain is a content address: chain()/
  extend_chain() graft externally prefilled pages, dedup racing
  installs back to the pool, and report registered/evicted deltas
  through drain_events() for the fleet directory
- a decode server pulls a prompt's pages from a prefill replica
  (SRV_PAGE_FETCH -> SRV_PAGES), installs them, and decodes BIT-EXACT
  (np.array_equal) against a colocated server that prefilled the same
  prompt itself; the prefill runs ONCE per unique prefix fleet-wide
  (the second fetch ships straight from the prefill PrefixCache) and a
  re-fetch of resident pages is a zero-byte local no-op
- a pushed SRV_PAGES shipment acks {installed, deduped}; pushing the
  same shipment again is a pure dedup ack; a shipment whose keys fail
  the receiver's own hash of the prompt is REFUSED (REPLY_ERR,
  nothing installed)
- the router's prefix directory follows replica truth: SRV_HEALTH
  new/evicted deltas add/prune entries, replica death forgets every
  entry wholesale, and a stale directory only ever nudges scoring —
  _pick_locked still dispatches to any healthy decode replica and
  never to the prefill tier
- every ship-path stage deducts elapsed deadline budget: a spent
  deadline or a dead peer is a typed ShipError (the caller re-prefills
  locally), never a hang
"""
import socket
import threading
import time

import numpy as np
import pytest

import fleet_worker as fw
from paddle_tpu import flags
from paddle_tpu.distributed import wire
from paddle_tpu.serving import LMServer, ReplicaServer, ShipError
from paddle_tpu.serving import disagg
from paddle_tpu.serving.fleet import FleetRequest, FleetRouter
from paddle_tpu.serving.paging import PagePool, PrefixCache, chain_keys

PT = 4                                    # page_tokens under test
PROMPT = [3, 9, 27, 17, 5, 41, 2, 8, 60, 33, 12, 7, 19]   # 3 full pages
GEN = 3                                   # 13 + 3 <= CFG.max_len


@pytest.fixture(scope='module')
def model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp('disagg_model'))
    fw.build_model(d)
    return d


def _paged_server(model_dir):
    return LMServer(model_dir, slots=2, page_tokens=PT,
                    kv_pages=33)


class _InprocReplica(object):
    def __init__(self, srv):
        self.rs = ReplicaServer(srv, '127.0.0.1:0')
        self.ep = '127.0.0.1:%d' % self.rs.port
        self._t = threading.Thread(target=self.rs.serve_forever,
                                   daemon=True)
        self._t.start()

    def stop(self):
        self.rs.shutdown()
        self._t.join(timeout=10)


# -- wire layer ------------------------------------------------------------

def test_srv_pages_round_trip_both_meta_codecs():
    keys = chain_keys(PROMPT, PT, limit=len(PROMPT) - 1)
    meta = {'seq': 5, 'keys': keys, 'skip': 1, 'prompt': PROMPT,
            'page_tokens': PT}
    val = np.arange(4 * 2 * PT * 2 * 2, dtype='f4').reshape(4, 2, PT,
                                                            2, 2)
    for version in (wire.WIRE_VERSION, wire.WIRE_VERSION_BMETA):
        buf = wire.pack_msg(wire.SRV_PAGES, meta, value=val,
                            version=version)
        (t, m, v), = wire.unpack_msgs(buf)
        assert t == wire.SRV_PAGES
        assert m['keys'] == keys and m['skip'] == 1
        assert m['prompt'] == PROMPT and m['page_tokens'] == PT
        assert v.dtype == np.float32 and np.array_equal(v, val)


def test_srv_page_fetch_round_trip_over_socketpair():
    a, b = socket.socketpair()
    try:
        have = chain_keys(PROMPT, PT, limit=len(PROMPT) - 1)[:1]
        wire.write_msg(a, wire.SRV_PAGE_FETCH,
                       {'seq': 1, 'have': have, 'deadline_ms': 250.0},
                       np.asarray(PROMPT, np.int64))
        t, m, v = wire.read_msg(b)
        assert t == wire.SRV_PAGE_FETCH
        assert m['have'] == have and m['deadline_ms'] == 250.0
        assert [int(x) for x in v] == PROMPT
    finally:
        a.close()
        b.close()


def test_srv_pages_crc_bit_flip_is_frame_corrupt():
    keys = chain_keys(PROMPT, PT, limit=len(PROMPT) - 1)
    val = np.ones((4, 3, PT, 2, 2), np.float32)
    for version in (wire.WIRE_VERSION, wire.WIRE_VERSION_BMETA):
        buf = bytearray(wire.pack_msg(
            wire.SRV_PAGES,
            {'seq': 1, 'keys': keys, 'skip': 0, 'prompt': PROMPT,
             'page_tokens': PT}, value=val, version=version))
        buf[-3] ^= 0x10                   # one bit, inside page bytes
        with pytest.raises(wire.FrameCorruptError):
            list(wire.unpack_msgs(bytes(buf)))
        # the streaming reader rejects it identically
        a, csock = socket.socketpair()
        try:
            a.sendall(bytes(buf))
            with pytest.raises(wire.FrameCorruptError):
                wire.read_msg(csock)
        finally:
            a.close()
            csock.close()


# -- paging layer: the content-addressed chain -----------------------------

def test_chain_extend_dedup_and_directory_deltas():
    pool = PagePool(17, PT)
    cache = PrefixCache(pool)
    keys = chain_keys(PROMPT, PT, limit=len(PROMPT) - 1)
    assert len(keys) == 3
    assert cache.chain(PROMPT, limit=len(PROMPT) - 1) == ([], [])
    ids = [pool.alloc() for _ in range(3)]
    cache.extend_chain(b'', [bytes.fromhex(k) for k in keys], ids)
    digests, pages = cache.chain(PROMPT, limit=len(PROMPT) - 1)
    assert [d.hex() for d in digests] == keys and pages == ids
    assert cache.drain_events() == {'new': keys, 'evicted': []}
    assert cache.resident_pages == 3
    # racing duplicate install: the resident pages win, the dup refs
    # go straight back to the pool, no delta announced
    dup = [pool.alloc() for _ in range(3)]
    in_use = pool.pages_in_use
    cache.extend_chain(b'', [bytes.fromhex(k) for k in keys], dup)
    assert pool.pages_in_use == in_use - 3
    assert cache.chain(PROMPT, limit=len(PROMPT) - 1)[1] == ids
    assert cache.drain_events() == {'new': [], 'evicted': []}
    # a graft onto a resident parent extends, not restarts, the chain
    longer = PROMPT + [44, 45, 46, 47, 48]          # 4th full page
    k4 = chain_keys(longer, PT, limit=len(longer) - 1)
    assert k4[:3] == keys
    p4 = pool.alloc()
    cache.extend_chain(bytes.fromhex(keys[-1]), [bytes.fromhex(k4[3])],
                       [p4])
    assert [d.hex() for d in
            cache.chain(longer, limit=len(longer) - 1)[0]] == k4
    assert cache.drain_events()['new'] == [k4[3]]
    # leaf-first eviction reports every dropped key for the directory
    gone = []
    while cache.evict_one():
        gone.extend(cache.drain_events()['evicted'])
    assert sorted(gone) == sorted(k4)
    pool.check()
    assert pool.pages_in_use == 0


# -- server layer: fetch/install ship path, bit-exact ----------------------

@pytest.mark.timeout(600)
def test_page_fetch_install_decode_bit_exact_and_prefill_once(model_dir):
    with _paged_server(model_dir) as ref:
        want = ref.generate(PROMPT, GEN)
    prefill = _paged_server(model_dir)
    prefill.generate([50, 51, 52], 1)     # warm the jit caches
    rep = _InprocReplica(prefill)
    d1 = _paged_server(model_dir)
    d2 = _paged_server(model_dir)
    try:
        base = prefill.stats()['kv']['prefix_misses']   # the warm-up's
        out = disagg.fetch_and_install(d1, rep.ep, PROMPT, timeout=120.0)
        assert out['fetched'] and out['installed'] == 3
        assert out['deduped'] == 0 and out['bytes'] > 0
        # the prompt's chain is resident now: a re-fetch never touches
        # the wire
        again = disagg.fetch_and_install(d1, rep.ep, PROMPT,
                                         timeout=120.0)
        assert again == {'installed': 0, 'deduped': 3, 'fetched': False,
                         'bytes': 0}
        # decode over the shipped pages: a PrefixCache hit, bit-exact
        # against the colocated server's own cold prefill
        got = d1.generate(PROMPT, GEN)
        assert np.array_equal(np.asarray(got, np.int64),
                              np.asarray(want, np.int64))
        assert d1.stats()['kv']['prefix_hits'] >= 1
        # prefill once per unique prefix FLEET-wide: the first fetch
        # cost the prefill tier exactly one prefill (one prefix miss);
        # a second decode replica's fetch ships from its PrefixCache
        # without running the model again
        assert prefill.stats()['kv']['prefix_misses'] == base + 1
        out2 = disagg.fetch_and_install(d2, rep.ep, PROMPT,
                                        timeout=120.0)
        assert out2['fetched'] and out2['installed'] == 3
        assert prefill.stats()['kv']['prefix_misses'] == base + 1
        got2 = d2.generate(PROMPT, GEN)
        assert np.array_equal(np.asarray(got2, np.int64),
                              np.asarray(want, np.int64))
    finally:
        rep.stop()
        for s in (prefill, d1, d2):
            s.close(drain=False)


@pytest.mark.timeout(600)
def test_srv_pages_push_dedup_ack_and_foreign_keys_refused(model_dir):
    src = _paged_server(model_dir)
    dst = _paged_server(model_dir)
    rep = _InprocReplica(dst)
    sock = None
    try:
        src.generate(PROMPT, 1)           # prefill registers the chain
        export = src.export_prefix(PROMPT)
        assert export is not None and len(export['keys']) == 3
        meta, val = disagg.pack_pages(PROMPT, export)
        assert meta['skip'] == 0 and val is not None
        host, port = rep.ep.rsplit(':', 1)
        sock = socket.create_connection((host, int(port)), timeout=30.0)
        sock.settimeout(120.0)
        wire.write_msg(sock, wire.SRV_PAGES, dict(meta, seq=1), val)
        t, m, _ = wire.read_msg(sock)
        assert t == wire.REPLY_OK
        assert m['installed'] == 3 and m['deduped'] == 0
        # the identical shipment again: pure dedup ack, nothing grafted
        wire.write_msg(sock, wire.SRV_PAGES, dict(meta, seq=2), val)
        t, m, _ = wire.read_msg(sock)
        assert t == wire.REPLY_OK
        assert m['installed'] == 0 and m['deduped'] == 3
        # keys that fail the receiver's own hash of the prompt are
        # refused outright — a corrupt/foreign shipment never installs
        bad = dict(meta, seq=3, keys=list(reversed(meta['keys'])))
        wire.write_msg(sock, wire.SRV_PAGES, bad, val)
        t, m, _ = wire.read_msg(sock)
        assert t == wire.REPLY_ERR
        assert 'hash chain' in m['error'] and m['retryable'] is False
    finally:
        if sock is not None:
            sock.close()
        rep.stop()
        src.close(drain=False)
        dst.close(drain=False)


# -- router layer: the fleet prefix directory ------------------------------

def test_prefix_directory_affinity_invalidation_and_stale_fallback():
    dec_ep, pre_ep = '127.0.0.1:1', '127.0.0.1:2'
    router = FleetRouter([dec_ep], prefill_replicas=[pre_ep])
    keys = chain_keys(PROMPT, PT, limit=len(PROMPT) - 1)
    try:
        with router._mu:
            dec = router._reps[dec_ep]
            pre = router._reps[pre_ep]
            assert dec.role == 'serve' and pre.role == 'prefill'
            router._dir_apply_locked(dec, {
                'page_tokens': PT, 'prefix_new': keys,
                'prefix_hits': 4, 'prefix_misses': 2,
                'pages_shipped': 7, 'ship_bytes': 1024})
            router._dir_apply_locked(pre, {'page_tokens': PT,
                                           'prefix_new': keys[:1]})
            assert router._prefix_dir[keys[0]] == {dec_ep, pre_ep}
            assert dec.prefix_hits == 4 and dec.pages_shipped == 7
            req = FleetRequest(PROMPT, GEN, None, None)
            assert router._affinity_locked(req, dec) == 1.0
            assert router._affinity_locked(req, pre) == \
                pytest.approx(1.0 / 3.0)
            # the prefill pick is affinity-first once the tier is
            # trustworthy, and the DECODE pick never returns it
            assert router._pick_prefill_locked(req) is None  # unhealthy
            pre.healthy = True
            assert router._pick_prefill_locked(req) is pre
            assert router._pick_locked(req) is None  # decode unhealthy
            dec.healthy = True
            assert router._pick_locked(req) is dec
            # a replica-reported eviction prunes exactly that entry
            router._dir_apply_locked(dec, {'page_tokens': PT,
                                           'prefix_evicted': [keys[2]]})
            assert keys[2] not in router._prefix_dir
            assert dec_ep in router._prefix_dir[keys[1]]
        # death forgets the replica's every entry wholesale...
        router._on_replica_down(pre)
        with router._mu:
            assert not any(pre_ep in eps
                           for eps in router._prefix_dir.values())
            # ...so no prefill peer is named and dispatch goes
            # colocated; the decode pick survives a directory that is
            # now stale ABOUT dec (affinity only nudges scoring)
            req2 = FleetRequest(PROMPT, GEN, None, None)
            assert router._pick_prefill_locked(req2) is None
            assert router._pick_locked(req2) is dec
        stats = router.stats()
        assert stats['prefill_replicas'] == 1
        assert stats['prefix_dir_entries'] == len(router._prefix_dir)
    finally:
        router.stop()


# -- ship-path failure typing ----------------------------------------------

class _StubSrv(object):
    """The two methods fetch_and_install touches before any socket."""

    def __init__(self, have=()):
        self._have = list(have)

    def stats(self):
        return {'kv': {'page_tokens': PT}}

    def resident_keys(self, prompt):
        return list(self._have)


def test_fetch_deadline_spent_is_ship_error():
    with pytest.raises(ShipError, match='deadline spent'):
        disagg.fetch_and_install(_StubSrv(), '127.0.0.1:9', PROMPT,
                                 deadline_at=time.perf_counter() - 0.01)


def test_fetch_dead_peer_is_ship_error():
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()                             # nobody listens here
    with pytest.raises(ShipError, match='page fetch from'):
        disagg.fetch_and_install(_StubSrv(), '127.0.0.1:%d' % port,
                                 PROMPT, timeout=2.0)


def test_fetch_full_local_hit_skips_the_wire():
    keys = chain_keys(PROMPT, PT, limit=len(PROMPT) - 1)
    out = disagg.fetch_and_install(_StubSrv(have=keys), '127.0.0.1:9',
                                   PROMPT)
    assert out == {'installed': 0, 'deduped': 3, 'fetched': False,
                   'bytes': 0}
