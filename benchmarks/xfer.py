"""Data-plane benchmark: peer-to-peer dependency fetches vs hauling
every result through the hub, emitted as BENCH_xfer.json — the CI gate
for the worker-to-worker data plane.

One seeded workload, run twice on the proc transport:

  * **hub mode**  — `inline_bytes` is set above every payload, so each
    producer uploads its result inline with `CompleteSteal` and every
    consumer pulls it back down from the hub (two copies per value
    through the single front door, the pre-data-plane behavior).
  * **peer mode** — `inline_bytes` is small, so producers advertise a
    location instead, and consumers dial the producing worker's data
    listener directly (one copy, off the hub).

The workload is transfer-bound by construction: producers emit
multi-hundred-KiB values, consumers fan them in from other workers
(producers are awaited before consumers are submitted, so values are
spread across the pool before anyone fetches).  Sink values are
digest-checked against a local model, so both modes also re-prove the
zero-loss contract end to end.

Gate (`--check`) asserts, with the usual 3-attempt / machine-scaled
rhythm of the other benchmark gates:

  * exact sink values in BOTH modes (zero loss);
  * peer mode really used the peer path (fetch count floor) and moved
    the payload traffic OFF the hub (hub-path bytes a small fraction of
    hub mode's);
  * peer mode is not slower than hub mode (ratio bound — machine speed
    cancels in the ratio) and the whole run stays within a loose
    machine-scaled multiple of the committed baseline wall clock.

Modes:
    (default)   run -> BENCH_xfer.json (+ stdout)
    --check     re-run and compare against the committed baseline
"""
from __future__ import annotations

import hashlib
import json
import sys
import time
from pathlib import Path

from repro.client import Client

REPO_ROOT = Path(__file__).resolve().parents[1]
BASELINE = REPO_ROOT / "BENCH_xfer.json"

N_PRODUCERS = 32
N_CONSUMERS = 32
PAYLOAD = 384 * 1024           # producer value size: well above 64 KiB
WORKERS = 4
ATTEMPTS = 3                   # best-of, per mode
PEER_RATIO_LIMIT = 1.25        # peer wall must stay within this x hub wall
HUB_BYTES_FRACTION = 0.25      # peer mode's hub-path payload budget
CHECK_WALL_TOLERANCE = 4.0     # loose absolute bound vs baseline


def _calibrate_us() -> float:
    """Machine-speed probe (same estimator as the other benchmark gates)."""
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        total = 0
        for i in range(100000):
            total += i * i
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _expected() -> list:
    """Local model of the DAG: producer i's value, and each consumer's
    digest over its two fan-in dependencies."""
    prods = [(hashlib.sha256(f"xfer{i}".encode()).digest()
              * (PAYLOAD // 32 + 1))[:PAYLOAD] for i in range(N_PRODUCERS)]
    sinks = []
    for j in range(N_CONSUMERS):
        a = prods[j % N_PRODUCERS]
        b = prods[(j * 7 + 3) % N_PRODUCERS]
        sinks.append(hashlib.md5(a + b).hexdigest())
    return sinks


def _run_mode(mode: str) -> dict:
    """One full DAG on the proc transport.  hub: payloads ride inline
    through the front door; peer: locations only, consumers dial the
    producing worker directly."""
    inline = (64 * 1024 * 1024) if mode == "hub" else 4096

    def make_producer(i):
        def fn():
            return (hashlib.sha256(f"xfer{i}".encode()).digest()
                    * (PAYLOAD // 32 + 1))[:PAYLOAD]
        return fn

    def make_consumer():
        def fn(a, b):
            return hashlib.md5(a + b).hexdigest()
        return fn

    t0 = time.perf_counter()
    with Client(transport="proc", workers=WORKERS, steal_n=2,
                heartbeat_s=0.2, inline_bytes=inline) as c:
        prods = [c.submit(make_producer(i), key=f"xp{i}")
                 for i in range(N_PRODUCERS)]
        # let every producer finish (values spread across the pool)
        # WITHOUT materializing them client-side — f.done() polls, so
        # the only payload motion measured is worker-to-worker
        c._ensure_running()
        deadline = time.monotonic() + 60
        while not all(f.done() for f in prods):
            if time.monotonic() > deadline:
                raise AssertionError(f"[{mode}] producers never finished")
            time.sleep(0.002)
        sinks = [c.submit(make_consumer(), prods[j % N_PRODUCERS],
                          prods[(j * 7 + 3) % N_PRODUCERS], key=f"xc{j}")
                 for j in range(N_CONSUMERS)]
        values = c.gather(sinks, timeout=120)
        snap = c.engine.data_plane.snapshot()
        by_path, lost = snap["by_path"], snap["lost"]
    wall = time.perf_counter() - t0
    if values != _expected():
        raise AssertionError(f"[{mode}] sink digests corrupted — the data "
                             "plane delivered wrong dependency bytes")
    return {
        "wall_s": round(wall, 4),
        "xfer_by_path": {p: {"n": n, "bytes": b, "total_s": round(t, 4)}
                         for p, (n, b, t) in sorted(by_path.items())},
        "lost": lost,
    }


def run() -> dict:
    best: dict = {}
    for mode in ("hub", "peer"):
        for _ in range(ATTEMPTS):
            meas = _run_mode(mode)
            if mode not in best or meas["wall_s"] < best[mode]["wall_s"]:
                best[mode] = meas
    peer = best["peer"]["xfer_by_path"].get("peer", {})
    hub_bytes_peer = best["peer"]["xfer_by_path"].get(
        "hub", {}).get("bytes", 0)
    out = {
        "n_producers": N_PRODUCERS, "n_consumers": N_CONSUMERS,
        "payload_bytes": PAYLOAD, "workers": WORKERS,
        "hub": best["hub"], "peer": best["peer"],
        "peer_fetches": peer.get("n", 0),
        "peer_bytes": peer.get("bytes", 0),
        "peer_mode_hub_bytes": hub_bytes_peer,
        "peer_vs_hub_wall": round(
            best["peer"]["wall_s"] / max(best["hub"]["wall_s"], 1e-9), 3),
        "wall_s": round(best["hub"]["wall_s"] + best["peer"]["wall_s"], 4),
        "calibration_us": round(_calibrate_us(), 1),
    }
    _assert_invariants(out)
    return out


def _assert_invariants(meas: dict):
    """Mode-shape invariants: true on every machine, every run.  (Hub
    mode shows NO fetches at all — its payloads ride inline through the
    hub inside completions and task metadata, which is exactly the haul
    the peer path removes; its cost shows up in the wall-clock ratio.)"""
    if meas["hub"]["lost"] or meas["peer"]["lost"]:
        raise AssertionError(f"value loss without any injected fault: "
                             f"hub={meas['hub']['lost']} "
                             f"peer={meas['peer']['lost']}")
    floor = N_CONSUMERS // 4
    if meas["peer_fetches"] < floor:
        raise AssertionError(
            f"peer mode barely used the peer path: {meas['peer_fetches']} "
            f"fetches < floor {floor}")
    budget = meas["peer_bytes"] * HUB_BYTES_FRACTION
    if meas["peer_mode_hub_bytes"] > budget:
        raise AssertionError(
            f"peer mode still hauled {meas['peer_mode_hub_bytes']}B of "
            f"payload through the hub (> {HUB_BYTES_FRACTION:.0%} of its "
            f"{meas['peer_bytes']}B peer traffic)")


def run_check() -> int:
    """CI gate: the data plane must move payload traffic off the hub and
    stay at least as fast, on seeded DAGs with exact-value checks."""
    baseline = json.loads(BASELINE.read_text())
    scale = 1.0
    base_cal = baseline.get("calibration_us")
    if base_cal:
        scale = min(max(_calibrate_us() / base_cal, 1.0), 4.0)
    wall_limit = baseline["wall_s"] * CHECK_WALL_TOLERANCE * scale
    print(f"machine-speed scale vs baseline: {scale:.2f}x "
          f"(wall limit {wall_limit:.1f}s)")
    last_err = None
    for attempt in range(3):
        try:
            meas = run()
        except AssertionError as e:
            last_err = e
            print(f"attempt {attempt + 1}: INVARIANT FAILED: {e}",
                  file=sys.stderr)
            time.sleep(2)
            continue
        ratio = meas["peer_vs_hub_wall"]
        ok_ratio = ratio <= PEER_RATIO_LIMIT   # machine speed cancels here
        ok_wall = meas["wall_s"] <= wall_limit
        print(f"xfer: peer {meas['peer']['wall_s']:.2f}s vs hub "
              f"{meas['hub']['wall_s']:.2f}s (ratio {ratio:.2f}, "
              f"limit {PEER_RATIO_LIMIT}) "
              f"peer_fetches={meas['peer_fetches']} "
              f"peer_mode_hub_bytes={meas['peer_mode_hub_bytes']} "
              f"wall={meas['wall_s']:.2f}s (limit {wall_limit:.1f}s) "
              f"{'OK' if ok_ratio and ok_wall else 'FAILED'}")
        if ok_ratio and ok_wall:
            return 0
        last_err = AssertionError(
            f"ratio {ratio} > {PEER_RATIO_LIMIT}" if not ok_ratio
            else f"wall {meas['wall_s']} > {wall_limit}")
        time.sleep(2)
    print(f"xfer gate failed: {last_err}", file=sys.stderr)
    return 1


if __name__ == "__main__":
    if "--check" in sys.argv:
        sys.exit(run_check())
    result = run()
    BASELINE.write_text(json.dumps(result, indent=1))
    print(json.dumps(result, indent=1))
    print(f"\nwrote {BASELINE}", file=sys.stderr)
