"""chip_smoke.py — does the served path still start on the chip?

Drives the system's main path once, through the entry points a user calls,
and checks the answers by the repo's own means. Three legs, each a child
process that owns the chip and has fully exited before the next starts
(this parent never initializes JAX — a chip belongs to one process):

  A  front door: `python -m openwhisk_tpu.standalone --balancer tpu`, driven
     over HTTP — create one python:3 action, 8 sequential + a burst of 16
     blocking invokes (inside the default 30-concurrent / 60-per-minute
     throttles), every answer checked, one activation record fetched back
     by id; then which kernel served and how it was chosen (by
     kernel_choice's static rule), and zero unexpected recompiles.
  B  fleet at real width: `python tools/loadgen.py --invokers 1024` —
     TpuBalancer.publish_many -> fused step -> readback -> bus -> 1,024
     echo invokers -> ack, the books growing on the device from the
     64-row pad; completions == offered, 0 errors.
  C  answers are right: on the device, decisions equal the plain reference
     (models/sharding_policy.py) at 1,024 invokers, every Pallas kernel
     equals its XLA twin at the shapes the balancer produces, and one
     fused step at the north-star geometry 65,536 x 256 matches the oracle.

Every child reports the platform it ran on and the run fails unless that is
`tpu`. The device programs of the default-on planes log-and-continue on
failure; a leg whose log carries such a line fails too. The last line of
stdout is one JSON object, printed only when every leg passed:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

    python chip_smoke.py            # exit 0 only on a TPU
"""
from __future__ import annotations

import argparse
import base64
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
PORT = 13233
#: seconds the three legs may take together (the contract allows 1200)
BUDGET_S = 1150.0

#: a logged-and-swallowed failure: the planes' device programs (shadow
#: step, quality scorer, telemetry fold, anomaly step/harvest, bucket
#: prewarm) and the dispatch/readback paths all say
#: "<what> failed: <why>" at WARN or ERROR; any ERROR line counts as well
_SWALLOWED = re.compile(r"\[ERROR\]|\[WARN\].*failed|Traceback \(most recent")


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def swallowed_failures(log_text: str) -> list:
    return [ln for ln in log_text.splitlines() if _SWALLOWED.search(ln)]


def cache_dir() -> str:
    """Where the children keep their compile cache (utils.config.boot_jax):
    JAX_COMPILATION_CACHE_DIR when set, else the fixed in-checkout path."""
    from openwhisk_tpu.utils.config import JAX_CACHE_DIR
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or JAX_CACHE_DIR


def cache_entries() -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir())
                   if not n.endswith("-atime"))
    except FileNotFoundError:
        return 0


def check_device(device, leg: str) -> dict:
    check(isinstance(device, dict) and device.get("platform") == "tpu",
          f"leg {leg} did not run on a TPU: device={device!r}")
    return {"platform": device["platform"], "kind": device["device_kind"],
            "count": device["device_count"]}


def stop_group(proc: subprocess.Popen, grace_s: float = 60.0) -> None:
    """SIGTERM the child, wait, then make sure nothing of its process group
    (the server's action-proxy sandboxes) outlives it."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def run_child(argv: list, err_path: str, what: str, timeout_s: float):
    """Run one JSON-printing child to its end: stdout's last line parsed,
    stderr spooled to `err_path` and returned, non-zero exit a failure,
    and nothing of its process group left behind."""
    with open(err_path, "wb") as err:
        proc = subprocess.Popen(argv, cwd=REPO, stdout=subprocess.PIPE,
                                stderr=err, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"{what} did not finish in {timeout_s:.0f}s")
    finally:
        stop_group(proc, grace_s=10.0)
    with open(err_path, errors="replace") as f:
        err_text = f.read()
    check(proc.returncode == 0,
          f"{what} exit code {proc.returncode}:\n{err_text[-3000:]}")
    return json.loads(out.decode().strip().splitlines()[-1]), err_text


# -- leg A: the front door -------------------------------------------------

ACTION_CODE = ("def main(args):\n"
               "    n = int(args.get('n', 0))\n"
               "    return {'n': n, 'square': n * n}\n")


def http(method: str, url: str, auth: str = None, body=None,
         timeout: float = 120.0):
    headers = {"Content-Type": "application/json"}
    if auth:
        headers["Authorization"] = "Basic " + base64.b64encode(
            auth.encode()).decode()
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def leg_a(workdir: str, timeout_s: float) -> dict:
    log_path = os.path.join(workdir, "standalone.log")
    base = f"http://127.0.0.1:{PORT}"
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "openwhisk_tpu.standalone",
             "--port", str(PORT), "--balancer", "tpu", "--no-ui"],
            cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True)
    try:
        def read_log() -> str:
            with open(log_path, errors="replace") as f:
                return f.read()

        deadline = time.monotonic() + min(300.0, timeout_s)
        while "standalone listening on" not in read_log():
            check(proc.poll() is None,
                  f"standalone exited {proc.returncode} at boot:\n"
                  + read_log()[-2000:])
            check(time.monotonic() < deadline, "standalone never came up")
            time.sleep(0.5)
        banner = read_log()
        auth = re.search(r"AUTH\s+(\S+:\S+)", banner).group(1)
        device = check_device(
            json.loads(re.search(r"DEVICE\s+(\{.*\})", banner).group(1)),
            "A")

        api = f"{base}/api/v1/namespaces/_"
        status, text = http("PUT", f"{api}/actions/smoke", auth,
                            {"exec": {"kind": "python:3",
                                      "code": ACTION_CODE}})
        check(status == 200, f"action create: {status} {text[:300]}")

        def invoke(n: int) -> str:
            status, text = http(
                "POST", f"{api}/actions/smoke?blocking=true", auth, {"n": n})
            check(status == 200, f"invoke n={n}: {status} {text[:300]}")
            record = json.loads(text)
            check(record["response"]["result"] == {"n": n, "square": n * n},
                  f"invoke n={n}: wrong result {record['response']!r}")
            return record["activationId"]

        ids = [invoke(n) for n in range(8)]
        with ThreadPoolExecutor(16) as pool:
            ids += list(pool.map(invoke, range(8, 24)))
        check(len(set(ids)) == 24, "activation ids are not distinct")

        # one activation record read back by id (the write races the ack)
        for _ in range(40):
            status, text = http("GET", f"{api}/activations/{ids[3]}", auth)
            if status == 200:
                break
            time.sleep(0.25)
        check(status == 200, f"activation fetch: {status} {text[:300]}")
        check(json.loads(text)["response"]["result"]
              == {"n": 3, "square": 9}, "activation record: wrong result")

        status, text = http("GET", f"{base}/admin/profile/kernel", auth)
        check(status == 200, f"/admin/profile/kernel: {status}")
        profile = json.loads(text)
        check_device(profile.get("device"), "A (/admin/profile/kernel)")
        check(profile["kernel_chosen_by"] == "static",
              f"kernel chosen by {profile['kernel_chosen_by']!r}, not by "
              f"the static rule")
        check(profile["compiles"]["unexpected"] == 0,
              f"unexpected recompiles: {profile['compiles']}")

        status, metrics = http("GET", f"{base}/metrics")
        check(status == 200, f"/metrics: {status}")
        served = re.findall(
            r"^openwhisk_loadbalancer_kernel_backend\{([^}]*)\} 1(?:\.0)?$",
            metrics, re.M)
        check(len(served) == 1, f"kernel_backend gauge: {served}")
        churn = re.findall(
            r'^openwhisk_loadbalancer_kernel_recompiles_total\{'
            r'expected="false"\} (\S+)$', metrics, re.M)
        check(all(float(v) == 0 for v in churn),
              f'expected="false" recompiles: {churn}')
        kernel = dict(re.findall(r'(\w+)="([^"]*)"', served[0]))
        check((kernel.get("backend"), kernel.get("placement"),
               kernel.get("chosen_by"))
              == (profile["kernel"], profile["placement_kernel"], "static"),
              f"kernel_backend gauge {kernel} disagrees with "
              f"/admin/profile/kernel")
    finally:
        stop_group(proc)
    check(proc.returncode in (0, -signal.SIGTERM),
          f"standalone exit code {proc.returncode}")
    with open(log_path, errors="replace") as f:
        bad = swallowed_failures(f.read())
    check(not bad, "standalone logged failures:\n" + "\n".join(bad[:20]))
    return {"device": device, "invokes": 24, "kernel": kernel,
            "compiles": profile["compiles"]["expected"]}


# -- leg B: the fleet at real width ----------------------------------------

def leg_b(workdir: str, timeout_s: float) -> dict:
    row, err_text = run_child(
        [sys.executable, os.path.join(REPO, "tools", "loadgen.py"),
         "--invokers", "1024", "--rate", "500", "--duration", "3"],
        os.path.join(workdir, "loadgen.err"), "loadgen", timeout_s)
    device = check_device(row.get("device"), "B")
    head = row["headline"]
    check(row["n_invokers"] == 1024, f"fleet width {row['n_invokers']}")
    check(head["errors"] == 0 and head["unfinished"] == 0
          and head["completed"] == head["offered"] > 0,
          f"loadgen headline: {head}")
    bad = swallowed_failures(err_text)
    check(not bad, "loadgen logged failures:\n" + "\n".join(bad[:20]))
    return {"device": device, "offered": head["offered"],
            "completed": head["completed"], "errors": head["errors"],
            "vmem_fallback_swap": "using the XLA kernel" in err_text}


# -- leg C: the answers are right (child mode: owns the chip) --------------

def leg_c_child() -> None:
    """Runs in the child: prints ONE JSON line; raises on any mismatch."""
    sys.path.insert(0, REPO)
    sys.path.insert(0, os.path.join(REPO, "tests", "performance"))
    from openwhisk_tpu.utils.config import boot_jax, device_info
    boot_jax()
    device = device_info()

    import jax.numpy as jnp
    import numpy as np

    import bench
    import warmhit
    from openwhisk_tpu.controller.loadbalancer.kernel_choice import (
        pallas_pair, xla_pair)
    from openwhisk_tpu.models.sharding_policy import ShardingPolicyState
    from openwhisk_tpu.ops.placement import (init_state,
                                             make_fused_step_packed,
                                             make_release_packed,
                                             schedule_batch,
                                             schedule_batch_repair,
                                             unpack_step_output)
    from openwhisk_tpu.ops.placement_pallas import (
        fits_vmem, fits_vmem_repair, schedule_batch_pallas,
        schedule_batch_repair_pallas, to_transposed)
    from tests.test_placement_kernel import (_batch_from_trace,
                                             _make_slot_allocator,
                                             _random_trace, _run_oracle)
    from tests.test_placement_repair import (_packed_buf, _random_batch,
                                             _random_state)

    out = {"device": device}

    # 1. decisions vs the plain reference at the full fleet width
    sim = warmhit.simulate(n_invokers=1024, rounds=6, batch=256,
                           n_actions=64)
    check(sim["decision_parity"] == 1.0, f"decision parity: {sim}")
    out["decision_parity_1024"] = sim["decision_parity"]

    # 2. XLA vs Pallas on the device: the bench's two-step books check,
    # then all four Pallas kernels against their XLA twins at the shapes
    # the balancer really produces (64 x 4096 is the standalone default —
    # a 64-wide lane axis; 1024 x 256 is the bench geometry) and at the
    # largest pow2 geometries the VMEM budget admits
    check(bench._parity_check(), "bench._parity_check: XLA/Pallas differ")
    cases = 0
    for n, a, b in ((64, 4096, 8), (64, 4096, 256), (1024, 256, 256),
                    (4096, 256, 256), (256, 4096, 256)):
        rng = np.random.RandomState(n + a + b)
        state = _random_state(n, rng, mem=2048, slots=a, conc_p=0.05)
        batch = _random_batch(n, b, rng, slots=min(a, 64))
        pens = (None, jnp.asarray(rng.randint(0, 3, n).astype(np.int32)))
        for pen in pens if n <= 1024 else pens[:1]:
            pairs = []
            if fits_vmem(n, a):
                pairs.append((schedule_batch, schedule_batch_pallas))
            if fits_vmem_repair(n, a, b):
                pairs.append((schedule_batch_repair,
                              schedule_batch_repair_pallas))
            check(pairs, f"no Pallas kernel admitted at {n}x{a} b={b}")
            for xla_fn, pallas_fn in pairs:
                x = xla_fn(state, batch, pen)
                p = pallas_fn(to_transposed(state), batch, penalty=pen)
                same = (np.array_equal(x[1], p[1])
                        and np.array_equal(x[2], p[2])
                        and np.array_equal(x[3], p[3])
                        and np.array_equal(x[0].free_mb, p[0].free_mb)
                        and np.array_equal(x[0].conc_free,
                                           np.asarray(p[0].conc_free).T)
                        and (len(x) < 5 or int(x[4]) == int(p[4])))
                check(same, f"{pallas_fn.__name__} != {xla_fn.__name__} at "
                      f"{n}x{a} b={b} penalized={pen is not None}")
                cases += 1
    out["pallas_vs_xla_cases"] = cases

    # 3. one fused step at the north-star geometry vs the oracle, through
    # the balancer's own kernel selection (B=32 resolves to repair) and
    # packed entry point; the trace reaches _mulmod's overflow regime
    n = 65536
    st = ShardingPolicyState.build([2048] * n)
    trace = _random_trace(24, 32, seed=64, conc_choices=(1, 4),
                          mems=(128, 256))
    batch = _batch_from_trace(st, trace, _make_slot_allocator())
    check(int(np.asarray(batch.step_inv).max()) * (n - 1) > 2 ** 31,
          "north-star trace does not reach the int32 overflow regime")
    sched, release, _ = xla_pair("auto")
    step = make_fused_step_packed(release, sched)
    rel = np.zeros((5, 32), np.int32)
    rel[3] = 1
    req = np.stack([np.asarray(c).astype(np.int32) for c in batch])
    buf = np.concatenate([rel.ravel(), np.zeros(3 * 64, np.int32),
                          req.ravel()])
    kstate = init_state(n, [st.invoker_slot_mb(2048)] * n, action_slots=256)
    kstate, packed = step(kstate, buf, 32, 64, 32)
    chosen, forced, _throttled, rounds, _warm, books = unpack_step_output(
        np.asarray(packed), 32)
    oracle = _run_oracle(st, trace)
    check([(int(c), bool(f)) for c, f in zip(chosen, forced)] == oracle,
          "north-star step: decisions differ from the oracle")
    check(np.array_equal(np.asarray(kstate.free_mb),
                         [i.semaphore.available_permits
                          for i in st.invokers]),
          "north-star step: books differ from the oracle")
    check(np.array_equal(books, np.asarray(kstate.free_mb)),
          "north-star step: the output's books differ from the state's")
    out["north_star"] = {"invokers": n, "action_slots": 256,
                         "requests": len(trace), "repair_rounds": rounds,
                         "books_bytes": int(kstate.conc_free.nbytes)}

    # 4. the books a step and a release fold return stay readable after
    # LATER donating calls have consumed the state they came from (the
    # balancer's readback worker converts them that late), on both
    # backends at the standalone geometry
    n, a, b = 64, 4096, 8
    for backend, (sched, release, _) in (("xla", xla_pair("auto")),
                                         ("pallas", pallas_pair("auto"))):
        rng = np.random.RandomState(31)
        bufs = [_packed_buf(rng, n, b, 64, b, slots=64) for _ in range(2)]
        rel = np.zeros((5, b), np.int32)
        rel[2], rel[3], rel[4] = 128, 1, 1    # 128 MB back to invoker 0
        want, got = [], []
        for donate, books in ((False, want), (True, got)):
            dstate = _random_state(n, np.random.RandomState(32), mem=2048,
                                   slots=a, conc_p=0.05)
            dstep = make_fused_step_packed(release, sched, donate=donate)
            dfold = make_release_packed(release, donate=donate)
            dstate, o1 = dstep(dstate, bufs[0], b, 64, b)
            dstate, o2 = dfold(dstate, rel)
            dstate, o3 = dstep(dstate, bufs[1], b, 64, b)
            books += [unpack_step_output(np.asarray(o1), b).books,
                      np.asarray(o2),
                      unpack_step_output(np.asarray(o3), b).books,
                      np.asarray(dstate.free_mb)]
        check(all(np.array_equal(w, g) for w, g in zip(want, got))
              and np.array_equal(got[2], got[3])
              and not np.array_equal(got[0], got[1]),
              f"{backend}: books read after later donating calls differ")
    out["donated_books_cases"] = 2
    print(json.dumps(out))


def leg_c(workdir: str, timeout_s: float) -> dict:
    row, _ = run_child(
        [sys.executable, os.path.abspath(__file__), "--leg-c-child"],
        os.path.join(workdir, "parity.err"), "parity child", timeout_s)
    row["device"] = check_device(row.get("device"), "C")
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--leg-c-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.leg_c_child:
        leg_c_child()
        return 0

    sys.path.insert(0, REPO)
    from openwhisk_tpu.utils.config import cpu_requested
    if cpu_requested(os.environ.get("JAX_PLATFORMS")):
        print("chip_smoke: JAX_PLATFORMS names cpu — this is the CPU twin, "
              "not the chip", file=sys.stderr)
        return 1

    devices = []
    t_start = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        for name, leg in (("A", leg_a), ("B", leg_b), ("C", leg_c)):
            before, t0 = cache_entries(), time.monotonic()
            try:
                # the legs share ONE budget, so a hung leg cannot carry
                # the run past the smoke's time limit
                result = leg(workdir, BUDGET_S - (t0 - t_start))
            except SmokeFailure as e:
                print(f"chip_smoke: leg {name} FAILED after "
                      f"{time.monotonic() - t0:.1f}s: {e}", file=sys.stderr)
                return 1
            devices.append(result.pop("device"))
            # every compile is stored (boot_jax zeroes the thresholds), so
            # new cache entries == programs compiled, not merely loaded
            after = cache_entries()
            print(json.dumps({"leg": name,
                              "wall_s": round(time.monotonic() - t0, 1),
                              "programs_compiled": after - before,
                              "cache_entries": after, **result}),
                  flush=True)
    if any(d != devices[0] for d in devices):
        print(f"chip_smoke: legs disagree on the device: {devices}",
              file=sys.stderr)
        return 1
    print(f"# chip_smoke passed in {time.monotonic() - t_start:.1f}s "
          f"(compile cache: {cache_dir()})", flush=True)
    print(json.dumps({"ok": True, "device": devices[0]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
