"""CI smoke for the HTTP serve API (serving/api.py): boot the server
in-process over a smoke-scale engine, then assert the three things a doc
example can't prove:

  * SSE tokens arrive INCREMENTALLY — the first streamed event lands while
    the engine is still mid-generation (checked against /v1/stats on a
    second connection), not in one burst after the request finishes
  * the streamed tokens are bit-identical to a direct Engine.submit
  * /v1/embeddings answers with the d_model-dim hidden state and a seeded
    sampled completion replays exactly

Runs on port 0 (OS-assigned), no subprocesses, exits non-zero on any
failed assertion. Usage: ``PYTHONPATH=src python scripts/http_smoke.py``.
"""

import http.client
import json
import sys

import jax
import numpy as np

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.models import init_model
from repro.serving import Engine, EngineConfig, serve_api

GEN = 24                      # long enough that streaming visibly overlaps
                              # generation on a fast smoke model


def _request(port, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    payload = json.dumps(body).encode() if body is not None else None
    conn.request(method, path, body=payload,
                 headers={"Content-Type": "application/json"} if payload else {})
    resp = conn.getresponse()
    data = json.loads(resp.read().decode())
    conn.close()
    return resp.status, data


def main() -> int:
    cfg = get_config("tinyllama-1.1b").smoke()
    with shd.use_mesh(shd.device_mesh(jax.devices()[0])):
        params = init_model(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(5)
        prompt = [int(t) for t in rng.integers(0, cfg.vocab, (8,))]

        # direct-engine reference stream, computed before the server exists
        ref_eng = Engine(cfg, params,
                         EngineConfig(max_slots=2, max_seq_len=64))
        ref = ref_eng.submit(prompt, GEN, strict=True)
        ref_eng.run_until_complete()
        expected = list(ref.tokens)
        ref_eng.close()

        eng = Engine(cfg, params, EngineConfig(max_slots=2, max_seq_len=64))
        srv = serve_api(eng, port=0)
        try:
            status, body = _request(srv.port, "GET", "/healthz")
            assert status == 200 and body == {"ok": True}, body
            print(f"# serve API up on {srv.url}")

            # --- SSE stream: incremental delivery + bit-identity ---------
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=60)
            conn.request("POST", "/v1/completions",
                         body=json.dumps({"prompt": prompt,
                                          "max_new_tokens": GEN,
                                          "stream": True}).encode(),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200, resp.status
            assert resp.getheader("Content-Type") == "text/event-stream"
            toks, mid_generation = [], None
            for raw in resp.fp:
                line = raw.decode().strip()
                if not line.startswith("data: "):
                    continue
                data = line[len("data: "):]
                if data == "[DONE]":
                    break
                event = json.loads(data)
                if "token" in event:
                    toks.append(event["token"])
                    if mid_generation is None:
                        # first event just landed: is the engine still
                        # decoding? (the incremental-delivery proof)
                        _, stats = _request(srv.port, "GET", "/v1/stats")
                        mid_generation = stats["tokens_generated"] < GEN
                else:
                    assert event.get("done") and event["n_tokens"] == GEN, \
                        event
            conn.close()
            assert toks == expected, "SSE stream != direct engine stream"
            assert mid_generation, \
                "first SSE event arrived only after generation finished"
            print(f"# PASS stream: {GEN} tokens, incremental, bit-identical "
                  f"to direct submit")

            # --- seeded sampled completion replays exactly ---------------
            req = {"prompt": prompt, "max_new_tokens": 8,
                   "temperature": 0.8, "top_k": 20, "top_p": 0.95,
                   "seed": 1234}
            _, first = _request(srv.port, "POST", "/v1/completions", req)
            _, again = _request(srv.port, "POST", "/v1/completions", req)
            assert first["tokens"] == again["tokens"], (first, again)
            print(f"# PASS sampling: seeded stream replayed exactly "
                  f"({first['tokens'][:4]}...)")

            # --- embeddings endpoint -------------------------------------
            status, body = _request(srv.port, "POST", "/v1/embeddings",
                                    {"prompt": prompt})
            assert status == 200 and body["dim"] == cfg.d_model, body
            print(f"# PASS embeddings: dim={body['dim']}")
            print("# http_smoke: ALL PASS")
            return 0
        finally:
            srv.close()
            eng.close()


if __name__ == "__main__":
    sys.exit(main())
