"""Shared driver for process-interleaved A/B measurements.

Two engines rarely fit HBM together, so the A/B protocol is: run each
variant in its own subprocess, interleaved (A B C A B C ...), keep each
variant's best window, and surface child failures (OOM kill, libtpu
abort, timeout) as explicit JSON error lines instead of silently
dropping the variant from the comparison.

One process per chip: the driver that calls ``run_interleaved`` must stay
off JAX (import nothing that opens a device) — a parent that holds the
chip makes every child fail to acquire it. Children run one at a time.
"""

import json
import subprocess


def run_interleaved(names, mk_cmd, rounds: int = 2, timeout: int = 1200):
    """Run ``mk_cmd(name)`` per variant, ``rounds`` times interleaved.

    Children print JSON lines; a dict with "error" passes through, a dict
    with "best_window_s" competes for the variant's best. Returns
    {name: best_dict}; prints every surviving best at the end.
    """
    best = {}
    for name in list(names) * rounds:
        try:
            r = subprocess.run(mk_cmd(name), capture_output=True,
                               text=True, timeout=timeout)
        except subprocess.TimeoutExpired as e:
            print(json.dumps({"variant": name,
                              "error": f"timeout after {timeout}s; "
                                       f"stdout tail: {str(e.stdout)[-200:]}"}),
                  flush=True)
            continue
        parsed = False
        for ln in r.stdout.strip().splitlines():
            try:
                d = json.loads(ln)
            except json.JSONDecodeError:
                continue
            parsed = True
            if "error" in d:
                print(ln, flush=True)
            elif d.get("variant") == name and "best_window_s" in d:
                if name not in best or \
                        d["best_window_s"] < best[name]["best_window_s"]:
                    best[name] = d
        if not parsed:
            # a child killed before its except clause (OOM kill, libtpu
            # abort) must not silently vanish from the comparison
            print(json.dumps({"variant": name,
                              "error": f"subprocess rc={r.returncode}, "
                                       f"no JSON: {r.stderr[-300:]}"}),
                  flush=True)
    for d in best.values():
        print(json.dumps(d), flush=True)
    return best
