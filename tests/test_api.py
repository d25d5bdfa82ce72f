"""HTTP serve API invariants (serving/api.py over Engine | Router):

  * bit-identity — tokens served over HTTP (streamed or not) are exactly the
                   tokens a direct Engine.submit produces, and a seeded
                   sampled completion returns the same stream on every call
  * streaming    — SSE events arrive in order (index 0..n-1), the terminal
                   frame carries finish_reason + n_tokens, and the stream
                   closes with ``data: [DONE]``
  * non-generative — /v1/embeddings returns the d_model-dim hidden state the
                   direct Engine.embed computes; /v1/classify softmaxes the
                   candidate token logits into a distribution
  * door contract — missing prompt / bad sampling params / unknown routes
                   are 4xx JSON errors, never hung sockets; /healthz and
                   /v1/stats serve while traffic decodes

The server is booted in-process on port 0 (OS-assigned). The serve loop
thread takes no mesh: the Engine enters its own device and mesh on every
call, whichever thread makes it.
"""

import http.client
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_model
from repro.serving import Engine, EngineConfig, serve_api

CFG = get_config("tinyllama-1.1b").smoke()
RNG = np.random.default_rng(31)


@pytest.fixture(scope="module")
def params():
    return init_model(CFG, jax.random.PRNGKey(0))


@pytest.fixture()
def api(params, mesh):
    """A fresh engine behind a port-0 API server, torn down per test."""
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    srv = serve_api(eng, port=0)
    yield srv, eng
    srv.close()
    eng.close()


def _request(srv, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
    payload = json.dumps(body).encode() if body is not None else None
    headers = {"Content-Type": "application/json"} if payload else {}
    conn.request(method, path, body=payload, headers=headers)
    resp = conn.getresponse()
    data = json.loads(resp.read().decode())
    conn.close()
    return resp.status, data


def _stream(srv, body):
    """POST a streaming completion, return the decoded SSE event list."""
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
    conn.request("POST", "/v1/completions",
                 body=json.dumps({**body, "stream": True}).encode(),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "text/event-stream"
    events = []
    for raw in resp.fp:
        line = raw.decode().strip()
        if not line.startswith("data: "):
            continue
        data = line[len("data: "):]
        if data == "[DONE]":
            break
        events.append(json.loads(data))
    conn.close()
    return events


def _prompt(n):
    return [int(t) for t in RNG.integers(0, CFG.vocab, (n,))]


def test_healthz_and_stats(api):
    srv, _ = api
    status, body = _request(srv, "GET", "/healthz")
    assert status == 200 and body == {"ok": True}
    status, body = _request(srv, "GET", "/v1/stats")
    assert status == 200
    assert body["submitted"] == 0
    status, _ = _request(srv, "GET", "/no/such/route")
    assert status == 404


def test_completion_matches_direct_engine(params, mesh):
    """The HTTP path is a transport, not a different decoder: greedy tokens
    over POST /v1/completions equal a direct Engine.submit bit for bit."""
    prompt = _prompt(6)
    ref_eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    ref = ref_eng.submit(prompt, 8, strict=True)
    ref_eng.run_until_complete()
    expected = list(ref.tokens)
    ref_eng.close()

    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    srv = serve_api(eng, port=0)
    try:
        status, body = _request(srv, "POST", "/v1/completions",
                                {"prompt": prompt, "max_new_tokens": 8})
        assert status == 200
        assert body["tokens"] == expected
        assert body["finish_reason"] == "length"
    finally:
        srv.close()
        eng.close()


def test_sse_stream_orders_and_terminates(api):
    srv, _ = api
    prompt = _prompt(5)
    events = _stream(srv, {"prompt": prompt, "max_new_tokens": 6})
    *toks, done = events
    assert [e["index"] for e in toks] == list(range(6))
    assert done == {"done": True, "finish_reason": "length", "n_tokens": 6}
    # the streamed tokens equal the non-streamed ones for the same prompt
    status, body = _request(srv, "POST", "/v1/completions",
                            {"prompt": prompt, "max_new_tokens": 6})
    assert status == 200 and body["tokens"] == [e["token"] for e in toks]


def test_seeded_sampling_is_reproducible_over_http(api):
    """Same seed, same stream — the batch-invariance counter survives the
    HTTP hop, so retries and replays are exact."""
    srv, eng = api
    req = {"prompt": _prompt(6), "max_new_tokens": 8,
           "temperature": 0.8, "top_k": 20, "top_p": 0.95, "seed": 1234}
    status, first = _request(srv, "POST", "/v1/completions", req)
    assert status == 200
    status, again = _request(srv, "POST", "/v1/completions", req)
    assert status == 200
    assert first["tokens"] == again["tokens"]
    assert eng.metrics.sampled_tokens >= 16


def test_stop_sequence_over_http(api):
    srv, _ = api
    prompt = _prompt(5)
    status, full = _request(srv, "POST", "/v1/completions",
                            {"prompt": prompt, "max_new_tokens": 8})
    assert status == 200 and len(full["tokens"]) == 8
    stop = full["tokens"][2:4]
    status, cut = _request(srv, "POST", "/v1/completions",
                           {"prompt": prompt, "max_new_tokens": 8,
                            "stop": [stop]})
    assert status == 200
    assert cut["tokens"] == full["tokens"][:4]
    assert cut["finish_reason"] == "stop"


def test_logprobs_over_http(api):
    """``"logprobs": true`` adds each token's log-probability (and
    ``"top_logprobs": k`` its k alternatives) from the very logits row the
    token choice used — no second forward. Strictly opt-in: responses
    without the flag carry exactly the pre-logprobs fields."""
    srv, _ = api
    prompt = _prompt(6)
    base = {"prompt": prompt, "max_new_tokens": 6}
    status, plain = _request(srv, "POST", "/v1/completions", base)
    assert status == 200
    assert set(plain) == {"tokens", "finish_reason"}   # nothing uninvited

    status, lp = _request(srv, "POST", "/v1/completions",
                          {**base, "logprobs": True, "top_logprobs": 2})
    assert status == 200
    assert lp["tokens"] == plain["tokens"]             # observation-free
    assert len(lp["logprobs"]) == len(lp["tokens"])
    assert all(v <= 0.0 for v in lp["logprobs"])
    assert all(len(row) == 2 for row in lp["top_logprobs"])
    # greedy decode: the chosen token is the argmax, so it heads every top
    # row with its own log-probability
    for tok, l, row in zip(lp["tokens"], lp["logprobs"], lp["top_logprobs"]):
        assert row[0][0] == tok and abs(row[0][1] - l) < 1e-6

    # "logprobs": true alone -> per-token values only, no top_logprobs key
    status, only = _request(srv, "POST", "/v1/completions",
                            {**base, "logprobs": True})
    assert status == 200 and "top_logprobs" not in only
    assert only["logprobs"] == lp["logprobs"]

    # streamed events carry the same fields riding each token event...
    events = _stream(srv, {**base, "logprobs": True, "top_logprobs": 2})
    *toks_ev, done = events
    assert [e["token"] for e in toks_ev] == lp["tokens"]
    assert [e["logprob"] for e in toks_ev] == lp["logprobs"]
    assert [e["top_logprobs"] for e in toks_ev] == lp["top_logprobs"]
    # ...and are absent from streams that did not ask
    events = _stream(srv, base)
    assert all("logprob" not in e for e in events[:-1])


def test_embeddings_match_direct_embed(params, mesh):
    prompt = _prompt(7)
    ref_eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    direct = ref_eng.embed(prompt)["embedding"]
    ref_eng.close()

    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    srv = serve_api(eng, port=0)
    try:
        status, body = _request(srv, "POST", "/v1/embeddings",
                                {"prompt": prompt})
        assert status == 200
        assert body["dim"] == CFG.d_model == len(body["embedding"])
        np.testing.assert_allclose(np.asarray(body["embedding"]),
                                   np.asarray(direct), rtol=1e-6)
        assert eng.metrics.embed_requests == 1
    finally:
        srv.close()
        eng.close()


def test_classify_is_a_distribution(api):
    srv, _ = api
    classes = [3, 17, 99]
    status, body = _request(srv, "POST", "/v1/classify",
                            {"prompt": _prompt(6), "classes": classes})
    assert status == 200
    assert body["classes"] == classes
    assert abs(sum(body["probs"]) - 1.0) < 1e-9
    assert body["top"] == classes[int(np.argmax(body["probs"]))]


def test_bad_requests_are_4xx(api):
    srv, _ = api
    status, body = _request(srv, "POST", "/v1/completions", {})
    assert status == 400 and "prompt" in body["error"]
    status, body = _request(srv, "POST", "/v1/completions",
                            {"prompt": _prompt(4), "temperature": -1.0})
    assert status == 400 and "temperature" in body["error"]
    status, body = _request(srv, "POST", "/v1/embeddings", {})
    assert status == 400
    status, body = _request(srv, "POST", "/v1/classify",
                            {"prompt": _prompt(4)})
    assert status == 400
    # over-budget requests hit the engine door -> strict QueueFull -> 400
    status, body = _request(srv, "POST", "/v1/completions",
                            {"prompt": _prompt(30), "max_new_tokens": 30})
    assert status == 400 and "rejected" in body["error"]
