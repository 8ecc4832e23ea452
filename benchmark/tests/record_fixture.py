"""Record the small trace the reduction is tested against (run on the chip).

    python benchmark/tests/record_fixture.py <out_dir>

Two jitted programs named as the system's are today (``jit_run``,
``jit__apply_cast``) run a few times with pauses between, under the same
``Tracer`` the harness uses.  Writes the ``.xplane.pb``, and beside it
``structure.json``: planes, lines and the first events of each, which is what
was looked at by hand, and ``expected.json``: what the harness clock saw.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmark.trace import Tracer, reduce_xplane

    def run(q, v):
        return jax.lax.top_k(q @ v.T, 16)

    def _apply_cast(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    run_j, apply_j = jax.jit(run), jax.jit(_apply_cast)
    q = jnp.ones((8, 256)); v = jnp.ones((65536, 256)); x = jnp.ones((256, 512)); w = jnp.ones((512, 512)) * 0.01
    jax.block_until_ready((run_j(q, v), apply_j(x, w)))
    os.makedirs(out_dir, exist_ok=True)
    tracer = Tracer(os.path.join(out_dir, "trace"))
    tracer.request_start()
    tracer.started.wait(60)
    t_a = time.monotonic()
    calls = []
    for i in range(5):
        t0 = time.monotonic()
        jax.block_until_ready(run_j(q, v))
        jax.block_until_ready(apply_j(x, w))
        calls.append([t0 - t_a, time.monotonic() - t_a])
        time.sleep(0.02)
    t_b = time.monotonic()
    tracer.wait(60)
    path = tracer.xplane_path()
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    structure = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({"line": line.name, "events": len(events), "first": [[e.name, int(e.start_ns), int(e.duration_ns)] for e in events[:4]]})
        structure.append({"plane": plane.name, "lines": lines})
    with open(os.path.join(out_dir, "structure.json"), "w") as f:
        json.dump(structure, f, indent=1)
    reduced = reduce_xplane(path, clip_mono=(t_a, t_b), mark_mono_ns=tracer.mark_mono_ns)
    whole = reduce_xplane(path)
    with open(os.path.join(out_dir, "expected.json"), "w") as f:
        json.dump({"clip_mono": [t_a, t_b], "mark_mono_ns": tracer.mark_mono_ns, "calls": calls, "reduced_clipped": reduced, "reduced_whole": whole, "bytes": os.path.getsize(path)}, f, indent=1)
    print(json.dumps({"bytes": os.path.getsize(path), "clipped": {k: reduced[k] for k in ("window_s", "busy_s", "clock", "modules")}, "whole": {k: whole[k] for k in ("window_s", "busy_s", "clock")}})[:3000])
    shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
