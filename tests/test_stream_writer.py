"""The one stream writer (``paddle_tpu/serving/streams.py``, PR 44): a
toy decoder behind ``serve()``, read over raw sockets so that the bytes
on the wire, and who waits for whom, are what is compared."""
import functools
import json
import os
import signal
import socket
import struct
import threading
import time

import pytest

import paddle_tpu as pt
from paddle_tpu import fault, layers
from paddle_tpu.inference import Predictor
from paddle_tpu.ops.registry import reset_op_seed
from paddle_tpu.serving import GenerationEngine, ServingEngine, serve
from paddle_tpu.serving.streams import stream_writer

MODEL = dict(vocab_size=97, hidden=32, num_layers=2, num_heads=4,
             num_kv_heads=2, intermediate=64)
KW = dict(num_slots=4, max_seq_len=512, attn_impl="xla", seed=0,
          queue_cap=64, deadline_ms=600000.0, page_tokens=16,
          prefill_chunk=0, prefix_reuse=False, speculate=False)
PROMPT = [3, 5, 7, 11, 13]


def limit(seconds):
    """The test's own time limit: SIGALRM raises in the test (tests run
    on a worker's main thread)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            def on_alarm(signum, frame):
                raise TimeoutError(f"{fn.__name__} ran over {seconds} s")
            old = signal.signal(signal.SIGALRM, on_alarm)
            signal.alarm(seconds)
            try:
                return fn(*args, **kwargs)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old)
        return wrapper
    return deco


def _mlp_predictor():
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        out = layers.fc(x, 4)
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    return Predictor(main, ["x"], [out], scope=scope)


def _serve(gen, **kw):
    gen.warmup()
    eng = ServingEngine(_mlp_predictor(), workers=1)
    eng.attach_generator(gen)
    return serve(eng, **kw)


@pytest.fixture(scope="module")
def served():
    """``(server, generator)``: four slots, contexts up to 512."""
    srv = _serve(GenerationEngine(MODEL, **KW))
    yield srv, srv.engine.generator
    srv.close()


def _post(srv, path, body: bytes, rcvbuf=None, headers=()):
    """A raw connection with the request sent and nothing read yet."""
    sock = socket.socket()
    if rcvbuf is not None:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    sock.settimeout(60)
    sock.connect((srv.host, srv.port))
    head = [f"POST {path} HTTP/1.1", f"Host: {srv.host}",
            "Content-Type: application/json", "Connection: close",
            f"Content-Length: {len(body)}", *headers]
    sock.sendall(("\r\n".join(head) + "\r\n\r\n").encode() + body)
    return sock


def _generate_body(n, prompt=PROMPT):
    return json.dumps({"prompt": prompt, "max_new_tokens": n,
                       "stream": True}).encode()


def _read_all(sock) -> bytes:
    chunks = []
    while True:
        chunk = sock.recv(65536)
        if not chunk:
            return b"".join(chunks)
        chunks.append(chunk)


def _stream(srv, n, prompt=PROMPT, path="/generate", body=None,
            headers=()):
    """``(header text, body bytes)`` of one stream read to its end."""
    sock = _post(srv, path, _generate_body(n, prompt) if body is None
                 else body, headers=headers)
    try:
        head, _, rest = _read_all(sock).partition(b"\r\n\r\n")
    finally:
        sock.close()
    return head.decode(), rest


def _lines(body: bytes):
    """``(token lines, summary)`` of a stream's body, parsed."""
    rows = [json.loads(ln) for ln in body.splitlines()]
    return rows[:-1], rows[-1]


def _parent_format(tokens, summary: dict) -> bytes:
    """What the per-handler loop wrote: ``json.dumps`` of each line."""
    return "".join(
        [json.dumps({"i": i + 1, "token": int(t)}) + "\n"
         for i, t in enumerate(tokens)]
        + [json.dumps(summary) + "\n"]).encode()


def _in_threads(fn, args_list, timeout=120):
    out = [None] * len(args_list)

    def run(i, args):
        out[i] = fn(*args)

    threads = [threading.Thread(target=run, args=(i, a), daemon=True)
               for i, a in enumerate(args_list)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
        assert not t.is_alive(), "a stream did not end"
    return out


def _drained(gen, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = gen.stats()
        if not st["slots_active"] and not st["queue_depth"]:
            return True
        time.sleep(0.02)
    return False


@limit(60)
def test_the_writer_thread_lives_with_its_servers():
    """The thread starts with the first stream and ends when the last
    server that held it has closed."""
    def writers():
        return [t for t in threading.enumerate()
                if t.name == "serving-stream-writer"]

    deadline = time.monotonic() + 30
    while writers():
        assert time.monotonic() < deadline
        time.sleep(0.05)
    reset_op_seed()
    srv = _serve(GenerationEngine(MODEL, **dict(KW, max_seq_len=64)))
    try:
        assert not writers()            # nothing streamed yet
        _, body = _stream(srv, 3)
        assert len(_lines(body)[0]) == 3
        assert len(writers()) == 1
    finally:
        srv.close()
    deadline = time.monotonic() + 30
    while writers():
        assert time.monotonic() < deadline
        time.sleep(0.05)


@limit(120)
def test_concurrent_streams_are_the_parents_bytes(served):
    """Six streams over four slots: each body is, byte for byte, the
    ``json.dumps`` lines the handler loop used to write, in order, with
    the summary last, ``streamed_tokens`` the number of lines and the
    lines' tokens the summary's."""
    srv, gen = served
    prompts = [PROMPT[:2 + i % 4] + [17 + i] for i in range(6)]
    budgets = [24, 9, 31, 16, 5, 40]
    want = [gen.generate(p, n, timeout=120)["tokens"]
            for p, n in zip(prompts, budgets)]
    got = _in_threads(_stream, [(srv, n, p)
                                for p, n in zip(prompts, budgets)])
    for (head, body), tokens, n in zip(got, want, budgets):
        assert head.startswith("HTTP/1.1 200")
        assert "Content-Type: application/x-ndjson" in head
        assert "Connection: close" in head and "Content-Length" not in head
        rows, summary = _lines(body)
        assert [r["token"] for r in rows] == tokens == summary["tokens"]
        assert [r["i"] for r in rows] == list(range(1, n + 1))
        assert summary["done"] is True and list(summary)[0] == "done"
        assert summary["streamed_tokens"] == len(rows) == n
        assert body == _parent_format(tokens, summary)


@limit(120)
def test_one_wakeup_a_booking_batch_not_a_token(served):
    """Four streams ride every pass of a four-slot grid: the writer
    wakes once a booking batch (a settled step, a prefill's first
    token) and twice a stream (its start, its summary), so lines a
    wake-up is about the live streams, where a handler a stream woke
    once a line."""
    srv, gen = served
    assert _drained(gen)
    n, streams = 120, 4
    w0, c0 = stream_writer.stats(), gen.stats()["counters"]
    got = _in_threads(_stream, [(srv, n)] * streams)
    assert _drained(gen)
    w1, c1 = stream_writer.stats(), gen.stats()["counters"]
    for _, body in got:
        rows, summary = _lines(body)
        assert len(rows) == n == summary["streamed_tokens"]
    lines = w1["lines"] - w0["lines"]
    wakeups = w1["wakeups"] - w0["wakeups"]
    batches = (c1["decode_steps"] - c0["decode_steps"]
               + c1["prefills"] - c0["prefills"])
    assert lines == n * streams
    assert 0 < wakeups <= batches + 2 * streams
    assert lines / wakeups >= streams - 1
    # a line a send at most (a wake-up that found two batches sends a
    # stream's two lines at once), and the summaries
    assert w1["sends"] - w0["sends"] <= lines + streams
    assert w1["open"] == 0


@limit(120)
def test_a_client_that_stops_reading_delays_nobody(served):
    """A client with a small receive buffer that never reads: its lines
    back up in its own backlog (``would_block`` counts the sends that
    left bytes behind), the other streams arrive whole and in the time
    they take alone, and the scheduler books the stuck stream's tokens
    all the same."""
    srv, gen = served
    assert _drained(gen)
    # accepted sockets take the listener's send buffer (and setting it
    # stops the kernel's autotuning): a few kilobytes fill it
    srv._httpd.socket.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 2048)
    t0 = time.monotonic()
    _stream(srv, 60)
    alone_s = time.monotonic() - t0
    w0 = stream_writer.stats()
    served0 = gen.stats()["counters"]["served"]
    stuck = _post(srv, "/generate", _generate_body(500), rcvbuf=1024)
    try:
        t0 = time.monotonic()
        got = _in_threads(_stream, [(srv, 60)] * 3)
        beside_s = time.monotonic() - t0
        for _, body in got:
            rows, summary = _lines(body)
            assert len(rows) == 60 == summary["streamed_tokens"]
        # the scheduler went on with the stuck stream's sequence
        deadline = time.monotonic() + 60
        while gen.stats()["counters"]["served"] < served0 + 4:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        w1 = stream_writer.stats()
        assert w1["would_block"] > w0["would_block"]
        assert w1["open"] == 1          # only the stuck one is left
        # four slots hold all four sequences: three beside a stuck
        # client take what one takes alone (a decode step serves every
        # slot), with room for a busy machine
        assert beside_s < 5 * alone_s + 2.0
        # once it reads, it gets every line and the summary, in order
        head, _, body = _read_all(stuck).partition(b"\r\n\r\n")
    finally:
        stuck.close()
    rows, summary = _lines(body)
    assert [r["i"] for r in rows] == list(range(1, 501))
    assert [r["token"] for r in rows] == summary["tokens"]
    assert summary["streamed_tokens"] == 500


def _access_records(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


@limit(120)
def test_a_client_that_hangs_up_mid_stream(served, tmp_path):
    """The client reads two lines and resets the connection: the
    sequence runs to its budget, nothing more is written, and the access
    record says ``client_gone`` with every token counted."""
    srv, gen = served
    assert _drained(gen)
    log = str(tmp_path / "access.jsonl")
    pt.set_flags({"FLAGS_telemetry": True,
                  "FLAGS_serving_access_log": log})
    try:
        c0 = gen.stats()["counters"]
        sock = _post(srv, "/generate", _generate_body(300))
        reader = sock.makefile("rb")
        while reader.readline() not in (b"\r\n", b""):
            pass                        # the headers
        first = [json.loads(reader.readline()) for _ in range(2)]
        assert [r["i"] for r in first] == [1, 2]
        # SO_LINGER 0: close() sends a reset, the next send fails
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
        reader.close()
        sock.close()
        assert _drained(gen)
        c1 = gen.stats()["counters"]
        assert c1["served"] == c0["served"] + 1
        assert c1["generated_tokens"] == c0["generated_tokens"] + 300
        deadline = time.monotonic() + 30
        recs = []
        while not recs:
            assert time.monotonic() < deadline
            time.sleep(0.05)
            recs = [r for r in _access_records(log)
                    if r["path"] == "/generate"] \
                if os.path.exists(log) else []
        assert recs[-1]["client_gone"] is True
        assert recs[-1]["streamed_tokens"] == 300
        assert recs[-1]["status"] == 200
        assert stream_writer.stats()["open"] == 0
    finally:
        pt.set_flags({"FLAGS_serving_access_log": ""})


@limit(120)
def test_request_timeout_ends_a_stream_with_the_error_summary():
    """``request_timeout_s`` passes while the sequence still decodes
    (every step delayed): the stream ends there with the error summary
    behind the token lines it had, and the sequence finishes unseen."""
    reset_op_seed()
    srv = _serve(GenerationEngine(MODEL, **KW), request_timeout_s=0.6)
    gen = srv.engine.generator
    try:
        fault.configure("decode_step:delay:100@1+")
        t0 = time.monotonic()
        head, body = _stream(srv, 30)
        took = time.monotonic() - t0
        rows, summary = _lines(body)
        assert head.startswith("HTTP/1.1 200")
        assert summary == {"done": True, "error": "request failed",
                           "detail": "stream timeout"}
        assert 0.5 <= took < 2.5
        assert 1 <= len(rows) < 30
        assert [r["i"] for r in rows] == list(range(1, len(rows) + 1))
        fault.reset()
        assert _drained(gen)
        assert gen.stats()["counters"]["generated_tokens"] == 30
        assert stream_writer.stats()["open"] == 0
        # a deadline spent before admission sheds as plain JSON:
        # nothing was streamed yet
        head, body = _stream(srv, 4,
                             headers=("X-PaddleTPU-Deadline-Ms: 0",))
        assert head.startswith("HTTP/1.1 503")
        assert "Content-Length" in head
        assert json.loads(body)["reason"] == "deadline"
        assert stream_writer.stats()["open"] == 0
    finally:
        fault.reset()
        srv.close()


BLOCK_MODEL = dict(
    vocab_size=97, hidden=64, num_layers=2, num_heads=4, num_kv_heads=2,
    intermediate=0, head_dim=32, rope_base=1e6, qk_norm=True,
    layer_pattern=[{"ffn": {"experts": 8, "top_k": 3, "width": 32,
                            "activation": "silu",
                            "route_from": "normed"}}],
    block_diffusion={"block": 4, "passes": 2, "mask_id": 96})


@limit(180)
def test_a_block_commit_arrives_as_four_lines():
    """A block-diffusion engine books a block's four tokens in one
    commit pass: they arrive as four lines, which share one ``send``."""
    srv = _serve(GenerationEngine(
        BLOCK_MODEL, num_slots=3, max_seq_len=64, prefill_buckets=[16, 32],
        page_tokens=8, prefill_chunk=0, prefix_reuse=False,
        speculate=False, attn_impl="xla"))
    gen = srv.engine.generator
    try:
        prompt = list(range(1, 11))
        want = gen.generate(prompt, 14, timeout=120)["tokens"]
        w0 = stream_writer.stats()
        head, body = _stream(srv, 14, prompt)
        w1 = stream_writer.stats()
        rows, summary = _lines(body)
        assert [r["token"] for r in rows] == want == summary["tokens"]
        assert [r["i"] for r in rows] == list(range(1, 15))
        assert body == _parent_format(want, summary)
        assert w1["lines"] - w0["lines"] == 14
        # the first block yields two (the prompt's tail fills its head),
        # then four a commit: four commits and the summary
        assert w1["sends"] - w0["sends"] <= 5
    finally:
        srv.close()


@limit(180)
def test_the_adoption_stream_runs_the_same_core():
    """``POST /adopt?stream=1`` on a decode-role replica: the segment's
    replayed token is the first line, the decoded ones follow, and the
    bytes are the colocated engine's stream."""
    dis = dict(KW, num_slots=2, max_seq_len=64, page_tokens=8,
               max_new_tokens=12)
    reset_op_seed()
    colocated = GenerationEngine(MODEL, **dis)
    want = colocated.generate(PROMPT, 12, timeout=120)["tokens"]
    colocated.close()
    reset_op_seed()
    pre = GenerationEngine(MODEL, role="prefill", **dis)
    reset_op_seed()
    srv = _serve(GenerationEngine(MODEL, role="decode", **dis))
    try:
        seg = pre.generate(PROMPT, 12, timeout=120)["segment"]
        w0 = stream_writer.stats()
        head, body = _stream(srv, 0, path="/adopt?stream=1&max_new_tokens=12",
                             body=seg.to_bytes())
        assert head.startswith("HTTP/1.1 200")
        rows, summary = _lines(body)
        assert [r["token"] for r in rows] == want == summary["tokens"]
        assert summary["streamed_tokens"] == 12
        assert body == _parent_format(want, summary)
        assert stream_writer.stats()["lines"] - w0["lines"] == 12
    finally:
        srv.close()
        pre.close()
