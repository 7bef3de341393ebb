"""Disaggregated prefill/decode serving: KV-segment handoff tests.

The contracts under test (README "Disaggregated serving"):

* **Bit-exactness** — export → transport → adopt → decode produces
  the IDENTICAL token stream AND logits (tolerance 0) as a colocated
  engine that ran prefill+decode itself, at page-boundary ±1 prompt
  lengths, through both the device and host-bytes transports, and
  with prefix reuse + chunked prefill active on the prefill side.
* **Refcount hygiene** — pools drain to zero live pages after
  adopt/finish/failure on both sides of the handoff; a pool that
  cannot hold a segment fails that request only.
* **Fingerprint contract** — a mismatched segment is rejected at
  adoption (SegmentMismatch), never queued, never decoded.
* **Affinity routing** — a role-split fleet routes /generate through
  prefill capacity into a pinned decode replica; an UNRELATED
  replica's ejection never disturbs a pinned stream; the
  cache-holding replica dying mid-generation surfaces the documented
  ``affinity_lost`` taxonomy (503/502 reason field), and is never
  silently re-prefilled unless ``FLAGS_disagg_reprefill=1``.
"""
import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import fault, layers
from paddle_tpu.inference import Predictor
from paddle_tpu.ops.registry import reset_op_seed
from paddle_tpu.serving import (DisaggPair, GenerationEngine,
                                HostBytesTransport, KVSegment,
                                RequestFailed, Router, RouterServer,
                                SegmentMismatch, ServingEngine, serve)

MODEL = dict(vocab_size=64, hidden=32, num_layers=2, num_heads=4,
             num_kv_heads=2, intermediate=64)
KW = dict(num_slots=2, max_seq_len=32, max_new_tokens=8,
          attn_impl="xla", seed=0, queue_cap=64, deadline_ms=600000.0,
          page_tokens=8, prefill_chunk=0,
          prefix_reuse=False)


def _build(role="both", **over):
    """Engine with weights identical across builds: the op-seed
    counter resets so every startup replays the same init sequence
    (what separate replica processes get for free)."""
    reset_op_seed()
    kw = dict(KW)
    kw.update(over)
    return GenerationEngine(MODEL, role=role, **kw)


@pytest.fixture(scope="module")
def colocated():
    eng = _build(keep_logits=True)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def pair():
    pre = _build("prefill", keep_logits=True)
    dec = _build("decode", keep_logits=True)
    p = DisaggPair(pre, dec, transport=HostBytesTransport())
    yield p
    p.close()


# ---------------------------------------------------------------------------
# segment codec
# ---------------------------------------------------------------------------

def test_segment_codec_roundtrip_and_rejects():
    rng = np.random.RandomState(0)
    layers_kv = [(rng.rand(3, 2, 8, 8).astype("<f4"),
                  rng.rand(3, 2, 8, 8).astype("<f4"))
                 for _ in range(2)]
    logits = rng.rand(1, 64).astype("<f4")
    seg = KVSegment("fp" * 12, 17, 17, [41], 8, layers_kv,
                    logits=logits, trace_id="t-1")
    buf = seg.to_bytes()
    back = KVSegment.from_bytes(buf)
    assert back.fingerprint == seg.fingerprint
    assert back.prompt_len == 17 and back.position == 17
    assert back.tokens == [41] and back.page_tokens == 8
    assert back.trace_id == "t-1"
    for (k0, v0), (k1, v1) in zip(layers_kv, back.layers):
        assert np.array_equal(k0, k1) and np.array_equal(v0, v1)
    assert np.array_equal(back.logits, logits)
    assert back.nbytes == seg.nbytes
    # corrupt framing is rejected, not mis-decoded
    with pytest.raises(ValueError, match="magic"):
        KVSegment.from_bytes(b"NOTASEG0" + buf[8:])
    with pytest.raises(ValueError, match="length mismatch"):
        KVSegment.from_bytes(buf[:-4])


# ---------------------------------------------------------------------------
# export -> adopt bit-exactness (the handoff core)
# ---------------------------------------------------------------------------

def test_export_adopt_bitexact_at_page_boundaries(colocated, pair):
    """Tokens AND logits identical (tolerance 0) through the full
    export → host-bytes transport → adopt → decode path, at prompt
    lengths page−1 / page / page+1 (pages of 8 tokens)."""
    rng = np.random.RandomState(1)
    for n in (7, 8, 9, 15, 16, 17):
        prompt = rng.randint(1, 64, size=n).tolist()
        want = colocated.generate(prompt, 6)
        got = pair.generate(prompt, 6, timeout=120)
        assert got["tokens"] == want["tokens"], (n, got, want)
        wl, gl = np.stack(want["logits"]), np.stack(got["logits"])
        assert wl.shape == gl.shape
        assert np.array_equal(wl, gl), \
            f"logit drift at prompt len {n}: {np.abs(wl - gl).max()}"
        assert got["handoff_ms"] is not None
        assert got["segment_bytes"] > 0


def test_export_adopt_with_prefix_reuse_and_chunked_prefill(colocated):
    """The prefill side runs chunked prefill AND shared-prefix reuse;
    exported segments still decode bit-exact — and the prefix index
    actually fired on the shared header (the interaction the
    acceptance bar names)."""
    pre = _build("prefill", keep_logits=True, prefill_chunk=8,
                 prefix_reuse=True, num_slots=2, num_pages=17)
    dec = _build("decode", keep_logits=True)
    p = DisaggPair(pre, dec, transport=HostBytesTransport())
    rng = np.random.RandomState(2)
    header = rng.randint(1, 64, size=16).tolist()   # two full pages
    try:
        for i in range(3):
            tail = rng.randint(1, 64, size=5 + i).tolist()
            prompt = header + tail
            want = colocated.generate(prompt, 5)
            got = p.generate(prompt, 5, timeout=120)
            assert got["tokens"] == want["tokens"], (i, got, want)
            assert np.array_equal(np.stack(want["logits"]),
                                  np.stack(got["logits"]))
        st = pre.stats()
        assert st["counters"]["prefix_hits"] >= 1, \
            "shared header never hit the prefill replica's index"
        assert st["counters"]["prefill_chunks"] >= 1, \
            "chunked prefill never ran"
        assert st["counters"]["segments_exported"] == 3
        assert dec.stats()["counters"]["segments_adopted"] == 3
    finally:
        p.close()


# ---------------------------------------------------------------------------
# refcounts + failure paths
# ---------------------------------------------------------------------------

def test_refcounts_balance_after_adopt_finish_and_failure(pair):
    pre, dec = pair.prefill, pair.decode
    rng = np.random.RandomState(3)
    for _ in range(3):
        pair.generate(rng.randint(1, 64, size=9).tolist(), 4,
                      timeout=120)
    assert pre.stats()["paged"]["pages_live"] == 0
    assert dec.stats()["paged"]["pages_live"] == 0
    # failure path: an injected adopt fault releases the pages and
    # fails exactly that request
    res = pre.generate(rng.randint(1, 64, size=9).tolist(), 4)
    seg = KVSegment.from_bytes(res["segment"].to_bytes())
    fault.configure("adopt:fail@1")
    try:
        with pytest.raises(RequestFailed, match="adopt failed"):
            dec.adopt(seg).result(60)
    finally:
        fault.configure("")
    assert dec.stats()["paged"]["pages_live"] == 0
    # ...and the same segment adopts cleanly afterwards (the failure
    # consumed nothing)
    out = dec.adopt(seg).result(60)
    assert out["tokens"][0] == res["tokens"][0]
    assert dec.stats()["paged"]["pages_live"] == 0


def test_fingerprint_mismatch_rejected_at_adoption(pair):
    res = pair.prefill.generate([5, 6, 7, 8, 9], 4)
    seg = res["segment"]
    bad = KVSegment("0" * 24, seg.prompt_len, seg.position,
                    seg.tokens, seg.page_tokens,
                    [(np.asarray(k), np.asarray(v))
                     for k, v in seg.layers])
    before = pair.decode.stats()["counters"]["adopt_rejects"]
    with pytest.raises(SegmentMismatch, match="fingerprint"):
        pair.decode.adopt(bad)
    assert pair.decode.stats()["counters"]["adopt_rejects"] \
        == before + 1
    # structural mismatch (wrong page geometry) is rejected too
    with pytest.raises(SegmentMismatch, match="structure"):
        wrong = KVSegment(pair.decode.fingerprint(), seg.prompt_len,
                          seg.position, seg.tokens, 4,
                          list(seg.layers))
        pair.decode.adopt(wrong)
    # a crafted prompt_len must be rejected BEFORE any allocation
    # keyed on it (a 10^12 header would otherwise OOM the replica)
    with pytest.raises(SegmentMismatch, match="structure"):
        huge = KVSegment(pair.decode.fingerprint(), 10 ** 12,
                         seg.position, seg.tokens, seg.page_tokens,
                         list(seg.layers))
        pair.decode.adopt(huge)


def test_role_guards_and_pool_too_small():
    pre = _build("prefill")
    with pytest.raises(ValueError, match="adopt"):
        pre.adopt(object())
    res = pre.generate([1] * 17, 2)   # 3 pages
    seg = res["segment"]
    # decode-role engines take segments, not prompts
    tiny = _build("decode", num_pages=3)  # 2 usable pages = 16 tokens
    try:
        with pytest.raises(ValueError, match="adopt"):
            tiny.submit([1, 2, 3])
        # a pool that cannot hold the segment even when idle fails
        # exactly that request (a requeue could never succeed)
        with pytest.raises(RequestFailed, match="adopt failed"):
            tiny.adopt(seg).result(60)
        assert tiny.stats()["paged"]["pages_live"] == 0
    finally:
        tiny.close()
        pre.close()


# ---------------------------------------------------------------------------
# affinity routing (in-process replicas behind a live router)
# ---------------------------------------------------------------------------

def _mlp_predictor():
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    startup.random_seed = main.random_seed = 0
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        out = layers.fc(x, 4, name="dis_f")
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    return Predictor(main, ["x"], [out], scope=scope)


def _replica(role, **over):
    gen = _build(role, **over)
    gen.warmup()
    eng = ServingEngine(_mlp_predictor(), workers=1)
    eng.attach_generator(gen)
    return serve(eng), gen


class _DyingDecodeStub(BaseHTTPRequestHandler):
    """Reports itself as a ready decode replica with zero load, then
    drops every /adopt connection after reading the body — the
    signature of the cache-holding replica dying mid-generation."""
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_GET(self):
        body = json.dumps({
            "status": "ok", "ready": True, "role": "decode",
            "generation": {"paged": {"pages_live": 0}},
            "serving": {"queue_depth": 0, "inflight_rows": 0}}).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0) or 0)
        self.rfile.read(n)
        self.connection.close()


def _stub_server():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _DyingDecodeStub)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def test_router_disagg_pipeline_and_unrelated_ejection(colocated):
    """End-to-end through a live router: non-stream and streamed
    /generate ride prefill → adopt bit-exact vs colocated; ejecting
    an UNRELATED replica mid-stream never disturbs the pinned decode
    (affinity survives), and zero affinity_lost is counted."""
    s_pre, g_pre = _replica("prefill")
    s_dec, g_dec = _replica("decode", max_new_tokens=24)
    s_other, _g_other = _replica("decode")   # the unrelated victim
    router = Router([s_pre.url, s_dec.url, s_other.url],
                    poll_interval_ms=100.0, autostart=False)
    server = RouterServer(router).start()
    try:
        router.poll_once()
        assert router.disagg_active()
        hz = router.healthz()[1]
        assert hz["disagg"] and hz["roles"].get("prefill") == 1
        prompt = [3, 5, 7, 11, 13]
        want = colocated.generate(prompt, 6)
        body = json.dumps({"prompt": prompt,
                           "max_new_tokens": 6}).encode()
        req = urllib.request.Request(
            server.url + "/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            doc = json.loads(r.read())
        assert doc["tokens"] == want["tokens"]
        # make the OTHER decode replica the loaded one so the pinned
        # stream lands on s_dec, then eject the other mid-stream
        other_rep = router._replicas[s_other.url]
        body = json.dumps({"prompt": prompt, "max_new_tokens": 6,
                           "stream": True}).encode()
        req = urllib.request.Request(
            server.url + "/generate", data=body,
            headers={"Content-Type": "application/json"})
        toks, done = [], None
        with urllib.request.urlopen(req, timeout=120) as r:
            for line in r:
                d = json.loads(line)
                if d.get("done"):
                    done = d
                else:
                    toks.append(d["token"])
                    # an unrelated ejection lands mid-stream: the
                    # pinned generation must not notice
                    with router._lock:
                        other_rep.ejected = True
        assert toks == want["tokens"], (toks, want["tokens"])
        assert done and done.get("error") is None
        assert done["tokens"] == want["tokens"]
        st = router.stats()["counters"]
        assert st["affinity_lost"] == 0
        assert st["disagg_generations"] == 2
    finally:
        server.close()
        s_pre.close()
        s_dec.close()
        s_other.close()


def test_affinity_lost_taxonomy_and_reprefill_flag(colocated):
    """The cache-holding decode replica dying mid-generation fails
    the request 502 ``affinity_lost`` (documented taxonomy, no silent
    re-prefill); with ``FLAGS_disagg_reprefill=1`` the router
    restarts the pipeline once on a surviving decode replica and the
    result stays bit-exact."""
    s_pre, _g = _replica("prefill")
    stub_httpd, stub_url = _stub_server()
    prompt = [3, 5, 7, 11]
    want = colocated.generate(prompt, 4)
    body = json.dumps({"prompt": prompt,
                       "max_new_tokens": 4}).encode()

    router = Router([s_pre.url, stub_url], poll_interval_ms=100.0,
                    autostart=False)
    server = RouterServer(router).start()
    try:
        router.poll_once()
        req = urllib.request.Request(
            server.url + "/generate", data=body,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=60)
        doc = json.loads(ei.value.read())
        assert ei.value.code == 502
        assert doc["reason"] == "affinity_lost"
        assert doc["error"] == "affinity_lost"
        st = router.stats()["counters"]
        assert st["affinity_lost"] == 1 and st["reprefills"] == 0
    finally:
        server.close()

    # reprefill: a healthy decode replica joins; the pipeline retries
    # exactly once and serves bit-exact
    s_dec, _g2 = _replica("decode")
    old = pt.get_flags("FLAGS_disagg_reprefill")["FLAGS_disagg_reprefill"]
    pt.set_flags({"FLAGS_disagg_reprefill": "1"})
    router2 = Router([s_pre.url, stub_url, s_dec.url],
                     poll_interval_ms=100.0, autostart=False)
    server2 = RouterServer(router2).start()
    try:
        router2.poll_once()
        hit_stub = False
        for _ in range(4):
            req = urllib.request.Request(
                server2.url + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                doc = json.loads(r.read())
            assert doc["tokens"] == want["tokens"]
            c = router2.stats()["counters"]
            if c["reprefills"]:
                hit_stub = True
                break
        assert hit_stub, "no request ever landed on the dying stub " \
                         "(reprefill path unexercised)"
        assert router2.stats()["counters"]["affinity_lost"] >= 1
    finally:
        pt.set_flags({"FLAGS_disagg_reprefill": old})
        server2.close()
        s_pre.close()
        s_dec.close()
        stub_httpd.shutdown()


# ---------------------------------------------------------------------------
# satellites: loadgen mixed distribution, fleet role validation
# ---------------------------------------------------------------------------

def _load_loadgen():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "serving_loadgen.py")
    spec = importlib.util.spec_from_file_location("slg_disagg", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_loadgen_mixed_prompt_dist():
    lg = _load_loadgen()
    make = lg.prompt_maker(64, 4, 8, 4.0, 8, pool=200, dist="bimodal",
                           prompt_dist="mixed", long_frac=0.25,
                           long_tokens=48)
    lens = [make(i)[0].size for i in range(200)]
    longs = [n for n in lens if n >= 36]
    shorts = [n for n in lens if n <= 8]
    assert longs and shorts, "mixed dist produced only one mode"
    assert len(longs) + len(shorts) == len(lens), \
        f"lengths outside both modes: {sorted(set(lens))}"
    assert all(36 <= n <= 48 for n in longs)
    assert 0.10 < len(longs) / len(lens) < 0.45
    with pytest.raises(ValueError, match="long_tokens"):
        lg.prompt_maker(64, 4, 8, 4.0, 8, prompt_dist="mixed",
                        long_tokens=0)
    with pytest.raises(ValueError, match="long_frac"):
        lg.prompt_maker(64, 4, 8, 4.0, 8, prompt_dist="mixed",
                        long_tokens=48, long_frac=1.5)


def test_decode_hop_requires_adopt_capability():
    """A 'both' replica whose health advertises no page pool (a build
    from before the dense cache went) must never win the adopt hop: its
    /adopt answers 404, which would turn a valid /generate into a
    client-visible error (pick() filters on the paged generation
    block, not the role alone)."""
    from paddle_tpu.serving.router import _Replica
    r = _Replica("http://x:1")
    r.health = {"status": "ok", "ready": True, "role": "both",
                "generation": {"paged": None}}
    r.health_ts = time.monotonic()
    assert r.serves(None) and r.serves("prefill")
    assert not r.serves("decode")
    r.health["generation"] = {"paged": {"pages_live": 0}}
    assert r.serves("decode")
    r.health["role"] = "decode"
    assert r.serves("decode") and not r.serves("prefill")


def test_fleet_roles_validation():
    from paddle_tpu.serving import FleetSupervisor
    with pytest.raises(ValueError, match="roles has"):
        FleetSupervisor(replicas=3, roles=["prefill"], autostart=False)
    with pytest.raises(ValueError, match="unknown role"):
        FleetSupervisor(roles=["prefill", "router"], autostart=False)
    sup = FleetSupervisor(roles=["prefill", "decode"], autostart=False)
    assert sup.n == 2
    assert [r.role for r in sup._replicas] == ["prefill", "decode"]
