"""CI smoke for the multi-process fleet (serving/transport.py +
serving/host_main.py + serving/api.py): boot TWO real worker processes
behind a Router behind the HTTP serve API, drive mixed concurrent traffic,
SIGKILL one worker mid-run, and assert the fleet recovers:

  * every HTTP completion still finishes with its full token count and
    ``finish_reason: length`` — the router re-placed the dead host's
    streams as continuations from the harvested tokens
  * the router ledger records exactly one LOST host and at least one
    re-admitted continuation
  * a replay of one of the served prompts returns the identical stream —
    determinism survives the crash and the re-placement
  * both worker processes are reaped on shutdown (the SIGKILLed one too)

The full fleet stats tree is dumped as a JSON artifact (``--out``) for CI
upload. Exits non-zero on any failed assertion.

With ``--disaggregate 1:1`` the fleet runs role-split (prefill host
admits, decode host continues shipped streams) and the SIGKILL victim is
the DECODE host once at least one stream has shipped to it: the router
must recover every shipped stream by re-prefill continuation on the
surviving prefill host — same full token counts, same replayed stream —
proving the fallback path end to end under a real process death.

Usage: ``PYTHONPATH=src python scripts/fleet_smoke.py
[--disaggregate 1:1] [--out reports/fleet_smoke_stats.json]``.
"""

import argparse
import http.client
import json
import os
import signal
import sys
import threading
import time
from pathlib import Path

import numpy as np

from repro.configs import get_config
from repro.serving import Router, RouterConfig, serve_api
from repro.serving.engine import EngineConfig
from repro.serving.router import parse_disaggregate
from repro.serving.transport import SubprocessTransport, build_model_spec

REQUESTS = 8
GEN = 128
PROMPT_LEN = 8


def _request(port, method, path, body=None, timeout=300):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    payload = json.dumps(body).encode() if body is not None else None
    conn.request(method, path, body=payload,
                 headers={"Content-Type": "application/json"} if payload
                 else {})
    resp = conn.getresponse()
    data = json.loads(resp.read().decode())
    conn.close()
    return resp.status, data


def _warm(fleet):
    """One tiny request per worker so every process compiles its
    executables before traffic starts (batch invariance: warmups change no
    other stream)."""
    for t in fleet:
        eid = t.submit(np.arange(4, dtype=np.int32), 2)
        deadline = time.monotonic() + 300
        while not t.poll({eid: 0}).get(eid, {}).get("done"):
            assert time.monotonic() < deadline, "worker warmup never finished"
            time.sleep(0.01)
        t.poll({}, drop=[eid])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="reports/fleet_smoke_stats.json",
                    help="where to dump the fleet stats JSON artifact")
    ap.add_argument("--disaggregate", default="",
                    help="role split spec (e.g. '1:1'): run prefill/decode "
                         "disaggregated and SIGKILL the DECODE host after "
                         "streams have shipped to it")
    args = ap.parse_args()
    roles = (parse_disaggregate(args.disaggregate, 2)
             if args.disaggregate else None)

    cfg = get_config("tinyllama-1.1b").smoke()
    spec = build_model_spec("tinyllama-1.1b", smoke=True, seed=0)
    # block shipping exports pool blocks, so disaggregation needs the
    # paged-native backend (same constraint serve.py enforces for
    # --disaggregate)
    paged = (dict(cache_backend="paged", paged_native=True, block_size=8)
             if roles else {})
    ecfg = EngineConfig(max_slots=2, max_queue=2 * REQUESTS,
                        max_seq_len=PROMPT_LEN + GEN, **paged)
    rng = np.random.default_rng(17)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab, (PROMPT_LEN,))]
               for _ in range(REQUESTS)]

    fleet = [SubprocessTransport(spec, ecfg) for _ in range(2)]
    victim = roles.index("decode") if roles else 0
    victim_pid = fleet[victim].pid
    print(f"# fleet up: worker pids {[t.pid for t in fleet]}"
          + (f", roles {roles}" if roles else ""))
    _warm(fleet)
    print("# workers warm (prefill/decode compiled)")

    router = Router(transports=fleet,
                    router_cfg=RouterConfig(
                        handoff_threshold=2 if roles else 0, roles=roles))
    srv = serve_api(router, port=0)
    results = [None] * REQUESTS

    def post(i):
        results[i] = _request(srv.port, "POST", "/v1/completions",
                              {"prompt": prompts[i], "max_new_tokens": GEN})

    try:
        threads = [threading.Thread(target=post, args=(i,))
                   for i in range(REQUESTS)]
        for th in threads:
            th.start()

        # kill the victim once the fleet is verifiably mid-run: some
        # tokens out, nowhere near done — and, disaggregated, only after
        # at least one stream has SHIPPED to the decode host, so the kill
        # provably lands on adopted streams
        total = REQUESTS * GEN
        deadline = time.monotonic() + 120
        while True:
            _, stats = _request(srv.port, "GET", "/v1/stats")
            done = stats["fleet"]["tokens_generated"]
            shipped = stats["router"].get("ships", 0)
            if 0 < done < total // 2 and (not roles or shipped >= 1):
                break
            assert done < total, "fleet finished before the kill landed"
            assert time.monotonic() < deadline, "fleet never got mid-run"
            time.sleep(0.005)
        os.kill(victim_pid, signal.SIGKILL)
        print(f"# SIGKILLed worker {victim_pid} at "
              f"{done}/{total} tokens generated"
              + (f", {shipped} streams shipped" if roles else ""))

        for th in threads:
            th.join(timeout=300)
        assert not any(th.is_alive() for th in threads), "HTTP requests hung"

        for i, (status, body) in enumerate(results):
            assert status == 200, f"request {i} failed: {body}"
            assert len(body["tokens"]) == GEN, (
                f"request {i}: {len(body['tokens'])} tokens != {GEN}")
            assert body["finish_reason"] == "length", body["finish_reason"]
        print(f"# PASS traffic: {REQUESTS} completions x {GEN} tokens, all "
              f"finished through the crash")

        # the serve-loop thread owns the router (api.py threading model) —
        # all stats reads go over HTTP, never router.stats() from here
        status, stats = _request(srv.port, "GET", "/v1/stats")
        assert status == 200, stats
        r = stats["router"]
        assert r["hosts_lost"] == 1, f"hosts_lost={r['hosts_lost']}"
        assert r["lost"] == [victim], f"lost={r['lost']}"
        assert r["recovered"] >= 1, f"recovered={r['recovered']}"
        if roles:
            # the decode host died holding shipped streams: they came back
            # by RE-PREFILL continuation on the surviving prefill host
            assert r["ships"] >= 1, f"ships={r['ships']}"
            print(f"# PASS disagg recovery: decode host {victim} LOST with "
                  f"{r['ships']} shipped streams, {r['recovered']} "
                  f"re-admitted by re-prefill on the prefill host")
        else:
            print(f"# PASS recovery: host {victim} LOST, {r['recovered']} "
                  f"streams re-admitted as continuations")

        # determinism survives the crash: a replay on the surviving fleet
        # returns the identical stream
        ref = results[0][1]["tokens"]
        status, replay = _request(srv.port, "POST", "/v1/completions",
                                  {"prompt": prompts[0],
                                   "max_new_tokens": GEN})
        assert status == 200, f"replay failed: {replay}"
        assert replay["tokens"] == ref, "replayed stream diverged"
        print("# PASS determinism: post-crash replay bit-identical")

        _, stats = _request(srv.port, "GET", "/v1/stats")   # final ledger
        stats["smoke"] = {
            "requests": REQUESTS, "gen": GEN,
            "disaggregate": args.disaggregate or None,
            "killed_host": victim,
            "killed_pid": victim_pid,
            "killed_at_tokens": done,
            "completions_ok": REQUESTS,
        }
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(stats, indent=2, default=str) + "\n")
        print(f"# wrote {out}")
    finally:
        srv.close()
        router.close()
    assert all(t.proc.poll() is not None for t in fleet), "orphan workers"
    print("# PASS shutdown: both workers reaped")
    print("# fleet_smoke: ALL PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
