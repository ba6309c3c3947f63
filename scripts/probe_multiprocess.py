"""Two-PROCESS distributed mesh probe (VERDICT r4 weak #7): each process
contributes 4 virtual CPU devices via jax.distributed, the multihost
mesh spans all 8, and a shard_map psum crosses the process boundary —
the DCN-analogue path executed for real (single machine, TCP transport).

Usage: python scripts/probe_multiprocess.py          (spawns its two workers)
       python scripts/probe_multiprocess.py --json   (machine-readable verdict)

The ``--json`` mode is the pod host-group tier's capability probe
(geomesa_tpu/pod/hostgroup.py): it always exits 0 and prints ONE json
line ``{"supported": ..., "verdict": "supported"|"UNSUPPORTED"|"error",
"reason": ...}`` — the distributed driver and its tests key off the
verdict (skip-not-fail on CPU backends without multi-process
collectives) instead of pattern-matching exit codes.

Run via the suite: tests/test_multihost_mesh.py::test_two_process_probe.
"""

import os
import subprocess
import sys
import time


def worker(pid: int, port: int):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
    )
    import numpy as np
    from jax.sharding import PartitionSpec as P

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from geomesa_tpu.parallel.dtable import _shard_map
    from geomesa_tpu.parallel.mesh import make_multihost_mesh

    mesh = make_multihost_mesh()  # 2 hosts x 4 devices, host-major
    assert mesh.devices.shape == (8,), mesh.devices.shape
    pids = [d.process_index for d in mesh.devices.ravel()]
    assert pids == sorted(pids), f"not host-major: {pids}"

    def body(x):
        return jax.lax.psum(x.sum(), "shard")

    fn = jax.jit(_shard_map(body, mesh, P("shard"), P()))
    import jax.numpy as jnp

    # each device holds one row; global array is process-sharded
    from jax.sharding import NamedSharding

    global_shape = (8, 128)
    local = np.full((4, 128), 1.0 + pid, np.float32)
    arrs = [
        jax.device_put(local[i : i + 1], d)
        for i, d in enumerate(jax.local_devices())
    ]
    x = jax.make_array_from_single_device_arrays(
        global_shape, NamedSharding(mesh, P("shard")), arrs
    )
    try:
        out = fn(x)
    except RuntimeError as e:
        if "aren't implemented on the CPU backend" in str(e):
            # this jax build's CPU client has no cross-process collective
            # transport: the probe is unsupported here, not failing
            print("UNSUPPORTED: no CPU multiprocess computations", flush=True)
            sys.exit(3)
        raise
    got = float(np.asarray(out)[()] if np.asarray(out).shape == () else np.asarray(out).ravel()[0])
    want = 128 * 4 * (1.0 + 2.0)  # both processes' rows in one psum
    assert abs(got - want) < 1e-3, (got, want)
    if pid == 0:
        print(f"PASS: cross-process psum = {got} (expected {want})", flush=True)


def probe() -> dict:
    """Launch the two workers and distill their exit codes into the
    machine-readable capability verdict (never raises):

    - ``supported``  — the cross-process psum ran and checked out;
    - ``UNSUPPORTED`` — a worker hit the backend's missing-collective
      error (exit 3): the environment can't run multi-process
      collectives, which is a skip, not a failure;
    - ``error``      — anything else (crash, timeout, port exhaustion).
    """
    try:
        rc = _launch_workers()
    except Exception as e:  # launcher infrastructure failure
        return {"supported": False, "verdict": "error",
                "reason": f"probe launcher failed: {e}", "worker_rcs": None}
    if not any(rc):
        return {"supported": True, "verdict": "supported",
                "reason": "two-process jax.distributed psum OK",
                "worker_rcs": rc}
    if 3 in rc:
        return {"supported": False, "verdict": "UNSUPPORTED",
                "reason": "no cross-process collectives on this backend "
                          "(CPU client without multiprocess computations)",
                "worker_rcs": rc}
    return {"supported": False, "verdict": "error",
            "reason": f"probe workers failed (rcs={rc})", "worker_rcs": rc}


def main():
    if sys.argv[1:2] == ["--json"]:
        import json

        print(json.dumps(probe()), flush=True)
        return
    if len(sys.argv) > 2:
        worker(int(sys.argv[1]), int(sys.argv[2]))
        return
    rc = _launch_workers()
    if not any(rc):
        print("two-process distributed probe: OK", flush=True)
        return
    if 3 in rc:
        # a worker reported UNSUPPORTED (see worker()): propagate the
        # distinct code so the suite can skip, not fail
        raise SystemExit(3)
    raise SystemExit(f"worker rcs: {rc}")


def _launch_workers() -> list:
    """Spawn the two workers; return their exit codes."""
    import socket

    for attempt in range(2):
        # fresh coordinator port per run: a fixed one collides with
        # an earlier run's TIME_WAIT/stale workers. bind-then-close
        # is racy (another process can grab the port before worker 0
        # binds it), hence the one retry with a new port.
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        procs = [
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), str(i), str(port)]
            )
            for i in range(2)
        ]
        try:
            # one shared 90 s deadline per attempt (not per worker):
            # two attempts total ~185 s, safely under the suite
            # wrapper's 240 s cap, so OUR finally-kill reaps the
            # workers rather than the test runner orphaning them
            # with the launcher
            attempt_deadline = time.monotonic() + 90
            rc = [
                p.wait(timeout=max(attempt_deadline - time.monotonic(), 1))
                for p in procs
            ]
        except subprocess.TimeoutExpired:
            rc = [1, 1]
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        if not any(rc) or 3 in rc:
            return rc
        if attempt == 0:
            print(f"worker rcs: {rc}; retrying on a fresh port", flush=True)
    return rc


if __name__ == "__main__":
    main()
