"""Operator reader for a run's liveness: heartbeat files or a live console.

``tpu_watch.py --status DIR`` reads the heartbeat files in a run's obs
directory (rdfind --trace DIR) and prints alive/wedged/done (+ the
stage/pass the run is inside), exiting 0/1/2 — the wedged-vs-slow verdict
for traced rdfind runs — or 3 (CORRUPT) when a host's heartbeat carries an
unrepaired integrity digest mismatch (the run may be moving, but its output
is not attested).  ``tpu_watch.py --console URL`` queries a live run console
(rdfind --console-port) instead.  Both take --json for one machine-readable
line.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))

sys.path.insert(0, REPO)
from rdfind_tpu.obs import flightrec, heartbeat  # noqa: E402 (after sys.path fix)


def _flightrec_summaries(obs_dir: str) -> dict:
    """Per-host flight-recorder dump summaries found next to the heartbeats
    (path + reason + event count + the last few event names) — the
    post-mortem pointer a wedge verdict should hand the operator."""
    out = {}
    for host, path in sorted(flightrec.find_dumps(obs_dir).items()):
        try:
            d = flightrec.load(path)
            events = d.get("events", [])
            out[host] = {
                "path": path,
                "reason": d.get("reason"),
                "dumped_at": d.get("dumped_at"),
                "n_events": d.get("n_events", len(events)),
                "last_events": [e.get("name") for e in events[-5:]],
            }
        except Exception as e:
            out[host] = {"path": path,
                         "error": f"{type(e).__name__}: {e}"}
    return out


def _degrading_hosts(hosts: dict) -> dict:
    """{host: forecast-advisory} for hosts whose heartbeat carries a
    cap-exhaustion forecast.  "Degrading" is a distinct verdict from
    "wedged": spans are still closing (the run is alive), but a cap is
    forecast to exhaust before the planned pass count — the degradation
    ladder (grow/split/skip) is about to fire, not a hang."""
    return {h: b["forecast"] for h, b in hosts.items()
            if isinstance(b.get("forecast"), dict)}


def _recovering_hosts(hosts: dict) -> dict:
    """{host: watchdog-verdict} for hosts whose heartbeat status says the
    collective watchdog fired and the run is re-entering via elastic resume
    (runtime/watchdog.py stamps ``recovering=True`` + ``watchdog=wedged@site``
    on fire; the supervisor clears the flag once a re-entered attempt
    completes).  RECOVERING is a distinct verdict from "wedged": the stall
    was already detected and converted to a preemption — the run is expected
    to come back on its own, so the exit code stays 0."""
    return {h: (b.get("watchdog") or "wedged@?") for h, b in hosts.items()
            if b.get("recovering") and not b.get("final")}


def _corrupt_hosts(hosts: dict) -> dict:
    """{host: integrity-verdict} for hosts whose heartbeat carries an
    unrepaired integrity-digest mismatch (obs/integrity.note_mismatch pushes
    it onto the run status).  CORRUPT is distinct from both "wedged" (the
    run may still be moving) and "degrading" (a cap forecast): the output
    of this run can no longer be trusted bit-for-bit."""
    return {h: b["integrity"] for h, b in hosts.items()
            if isinstance(b.get("integrity"), dict)
            and b["integrity"].get("corrupt")}


def _serving_stale_hosts(hosts: dict) -> dict:
    """Hosts running a serving process whose bundle dir holds a NEWER
    committed index generation than the one loaded (a pending or refused
    hot swap — the answers are correct but out of date).  A serve host is
    never "wedged" (idle is its steady state); staleness is its verdict."""
    out = {}
    for h, b in hosts.items():
        if b.get("mode") != "serve" or b.get("final"):
            continue
        gen, bgen = b.get("generation"), b.get("bundle_generation")
        behind = bgen is not None and (gen is None or bgen > gen)
        if b.get("index_stale") or behind:
            out[h] = {"generation": gen, "bundle_generation": bgen,
                      "pending": b.get("pending_swap")}
    return out


def _slo_burning_hosts(hosts: dict) -> dict:
    """Hosts running a serving process whose SLO engine reports a
    sustained burn (both burn-rate windows over target — obs/servestats).
    SLO-BURNING is distinct from SERVING-STALE: staleness says the data
    is out of date; a burn says the service itself (latency, errors, or
    freshness) is violating its target right now."""
    out = {}
    for h, b in hosts.items():
        if b.get("mode") != "serve" or b.get("final"):
            continue
        slo = b.get("slo")
        if isinstance(slo, dict) and slo.get("state") == "burning":
            out[h] = slo
    return out


def report_status(obs_dir: str, stale_s: float, as_json: bool = False) -> int:
    """The wedged-vs-slow verdict over a run's obs directory (exit codes:
    0 alive/done, 1 wedged, 2 no heartbeat at all, 3 CORRUPT — an
    unrepaired integrity mismatch on some host's heartbeat; "degrading" and
    "recovering" are reported but never change the exit code — the run is
    still making progress, or is expected to come back via elastic
    resume)."""
    verdict = heartbeat.assess(obs_dir, stale_s=stale_s)
    state = verdict["state"]
    hosts = {
        h: {**b, "stale": b["age_s"] > stale_s and not b.get("final")}
        for h, b in verdict["hosts"].items()}
    degrading = _degrading_hosts(hosts)
    recovering = _recovering_hosts(hosts)
    corrupt = _corrupt_hosts(hosts)
    serving_stale = _serving_stale_hosts(hosts)
    slo_burning = _slo_burning_hosts(hosts)
    recs = _flightrec_summaries(obs_dir)
    if as_json:
        print(json.dumps({"dir": obs_dir, "state": state,
                          "degrading": bool(degrading),
                          "recovering": bool(recovering),
                          "corrupt": bool(corrupt),
                          "serving_stale": bool(serving_stale),
                          "slo_burning": bool(slo_burning),
                          "stale_s": stale_s, "age_s": verdict["age_s"],
                          "hosts": hosts, "flightrec": recs},
                         sort_keys=True, default=str))
        if state == "missing":
            return 2
        if corrupt:
            return 3
        return 1 if state == "wedged" else 0
    if state == "missing":
        print(f"status[{obs_dir}]: no heartbeat files "
              f"(not a traced run directory, or the run never started)")
        return 2
    for h, b in sorted(hosts.items()):
        where = b.get("stage")
        if b.get("pass") is not None:
            where = f"{where} pass {b.get('pass')}"
        # Run mode rides the heartbeat status (runtime/delta.py sets
        # mode=delta + the base generation; the driver sets mode=full), so
        # an operator can tell an incremental replay from a full rebuild
        # without reading the run's stats.
        if b.get("mode") == "delta":
            where = f"{where} [delta, base gen {b.get('generation')}]"
        elif b.get("mode") == "serve":
            where = f"{where} [serve, gen {b.get('generation')}]"
        elif b.get("mode"):
            where = f"{where} [{b.get('mode')}]"
        flags = (" (final)" if b.get("final") else
                 " (STALE)" if b["stale"] else "")
        print(f"status[{obs_dir}] host {h}: last event {b['age_s']}s ago "
              f"in {where}" + flags)
        util = b.get("cap_util")
        if isinstance(util, dict):
            caps = ", ".join(f"{k}={v}" for k, v in sorted(util.items())
                             if k != "pass")
            print(f"status[{obs_dir}] host {h}: cap utilization "
                  f"(pass {util.get('pass')}): {caps}")
        wd = recovering.get(h)
        if wd is not None:
            print(f"status[{obs_dir}] host {h}: RECOVERING — collective "
                  f"watchdog fired ({wd}); converted to a preemption, "
                  f"elastic resume re-entering")
        fc = degrading.get(h)
        if fc is not None:
            print(f"status[{obs_dir}] host {h}: DEGRADING — cap "
                  f"{fc.get('cap')} forecast exhausted at pass "
                  f"{fc.get('predicted_pass')} ({fc.get('reason')}, frac "
                  f"{fc.get('frac')})")
        iv = corrupt.get(h)
        if iv is not None:
            print(f"status[{obs_dir}] host {h}: CORRUPT — integrity digest "
                  f"mismatch at {iv.get('site')} ({iv.get('stage')}); the "
                  f"output is not digest-attested")
        sv = serving_stale.get(h)
        if sv is not None:
            why = (f"; last swap refused: {sv['pending']}"
                   if sv.get("pending") else "")
            print(f"status[{obs_dir}] host {h}: SERVING-STALE — bundle "
                  f"dir committed generation {sv['bundle_generation']} "
                  f"but the server still answers from "
                  f"{sv['generation']}{why}")
        if b.get("mode") == "serve" and not b.get("final") \
                and "index_age_s" in b:
            print(f"status[{obs_dir}] host {h}: freshness — index age "
                  f"{b.get('index_age_s')}s, staleness "
                  f"{b.get('staleness_s')}s, "
                  f"{b.get('generations_behind')} generation(s) behind")
        burn = slo_burning.get(h)
        if burn is not None:
            print(f"status[{obs_dir}] host {h}: SLO-BURNING — "
                  f"{burn.get('slo')} SLO over target on both burn-rate "
                  f"windows (the service is violating its target now, "
                  f"not momentarily)")
    # Surface the wedged host's flight recorder when one was dumped: the
    # ring of events leading into the stall, captured even with the jsonl
    # tracer off.
    for h, r in sorted(recs.items()):
        if "error" in r:
            print(f"status[{obs_dir}] host {h}: flight recorder at "
                  f"{r['path']} unreadable ({r['error']})")
            continue
        print(f"status[{obs_dir}] host {h}: flight recorder "
              f"({r['n_events']} events, reason={r['reason']!r}) at "
              f"{r['path']}; last: {', '.join(map(str, r['last_events']))}")
    tail = ""
    if corrupt:
        tail = (f" (CORRUPT: unrepaired integrity mismatch on host(s) "
                f"{sorted(corrupt)})")
    elif state == "wedged":
        tail = f" (no span boundary for > {stale_s:.0f}s — wedged, not slow)"
    elif recovering:
        tail = (" (RECOVERING: collective watchdog fired on host(s) "
                f"{sorted(recovering)} — wedge already converted to a "
                "preemption, elastic resume in flight)")
    elif degrading:
        tail = (" (degrading: cap-exhaustion forecast active on host(s) "
                f"{sorted(degrading)} — alive, but the degradation ladder "
                "is imminent)")
    elif slo_burning:
        names = sorted({v.get("slo") for v in slo_burning.values()})
        tail = (f" (SLO-BURNING: host(s) {sorted(slo_burning)} over "
                f"target on {', '.join(map(str, names))} — sustained "
                "burn, not a spike)")
    elif serving_stale:
        tail = (" (SERVING-STALE: host(s) "
                f"{sorted(serving_stale)} answer from an older generation "
                "than the bundle dir holds — swap pending or refused)")
    print(f"status[{obs_dir}]: {state}" + tail)
    if corrupt:
        return 3
    return 1 if state == "wedged" else 0


def report_console(url: str, as_json: bool = False) -> int:
    """Client mode for the live run console (rdfind --console-port): fetch
    /status and /progress over HTTP and print the same alive/degrading
    verdict shape as --status, but from the running process itself (exit
    codes: 0 reachable, 1 run wedged per its own heartbeats, 2
    unreachable)."""
    import urllib.error
    import urllib.request
    base = url if "://" in url else "http://" + url
    base = base.rstrip("/")
    try:
        with urllib.request.urlopen(base + "/status", timeout=10) as r:
            status = json.load(r)
        with urllib.request.urlopen(base + "/progress", timeout=10) as r:
            progress = json.load(r)
    except (urllib.error.URLError, OSError, ValueError) as e:
        print(f"console[{url}]: unreachable ({e})")
        return 2
    if as_json:
        print(json.dumps({"url": base, "status": status,
                          "progress": progress}, sort_keys=True, default=str))
    else:
        hb = status.get("heartbeat") or {}
        state = hb.get("state", "serving")
        where = progress.get("run_stage")
        if progress.get("run_pass") is not None:
            where = f"{where} pass {progress['run_pass']}"
        print(f"console[{base}]: pid {status.get('pid')} {state}, in {where}")
        si = status.get("serving_index")
        if isinstance(si, dict):
            print(f"console[{base}]: index generation "
                  f"{si.get('generation')} (bundle dir has "
                  f"{si.get('bundle_generation')}), {si.get('n_cinds')} "
                  f"CINDs, {si.get('swaps')} swap(s), "
                  f"{si.get('refusals')} refusal(s)")
            fresh = si.get("freshness")
            if isinstance(fresh, dict):
                print(f"console[{base}]: freshness — index age "
                      f"{fresh.get('index_age_s')}s, staleness "
                      f"{fresh.get('staleness_s')}s, "
                      f"{fresh.get('generations_behind')} generation(s) "
                      f"behind")
            slo = status.get("slo")
            if isinstance(slo, dict):
                which = f" ({slo.get('slo')})" if slo.get("slo") else ""
                label = str(slo.get("state", "ok")).upper() \
                    if slo.get("state") != "ok" else "ok"
                print(f"console[{base}]: SLO {label}{which}")
            if si.get("stale"):
                why = (f"; last candidate: {si.get('pending')}"
                       if si.get("pending") else "")
                print(f"console[{base}]: SERVING-STALE — bundle dir "
                      f"committed generation {si.get('bundle_generation')} "
                      f"but the server still answers from "
                      f"{si.get('generation')}{why}")
            for link in si.get("chain") or []:
                print(f"console[{base}]: cert chain gen "
                      f"{link.get('generation')}: output "
                      f"{link.get('output_digest')} (base "
                      f"{link.get('base_output_digest')})")
        util = progress.get("cap_utilization") or {}
        for cap, row in sorted(util.items()):
            if isinstance(row, dict):
                print(f"console[{base}]: cap {cap}: used "
                      f"{row.get('used')}/{row.get('planned')} "
                      f"(frac {row.get('frac')})")
        for cap, adv in sorted((progress.get("cap_forecast") or {}).items()):
            print(f"console[{base}]: DEGRADING — cap {cap} forecast "
                  f"exhausted at pass {adv.get('predicted_pass')}"
                  f"/{adv.get('n_pass')} ({adv.get('reason')})")
    return 1 if (status.get("heartbeat") or {}).get("state") == "wedged" \
        else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--status", default=None, metavar="DIR",
                    help="read the heartbeat files in an obs directory (a "
                         "--trace DIR) and report alive/wedged/done")
    ap.add_argument("--stale-s", type=float,
                    default=heartbeat.DEFAULT_STALE_S,
                    help="--status: heartbeat age above which a run counts "
                         "as wedged")
    ap.add_argument("--json", action="store_true",
                    help="--status/--console: emit one machine-readable "
                         "JSON line (state + per-host staleness + "
                         "flight-recorder dump summaries) instead of prose")
    ap.add_argument("--console", default=None, metavar="URL",
                    help="query a live run console (rdfind --console-port) "
                         "at URL (host:port or http://...) instead of "
                         "reading heartbeat files: prints stage/pass, "
                         "per-cap utilization, and any cap-exhaustion "
                         "forecast (degrading ≠ wedged)")
    args = ap.parse_args()
    if args.console is not None:
        return report_console(args.console, as_json=args.json)
    if args.status is not None:
        return report_status(args.status, args.stale_s, as_json=args.json)

    ap.error("pass --status DIR or --console URL")


if __name__ == "__main__":
    sys.exit(main())
