#!/usr/bin/env python3
"""Record the small chip trace that `bench/tests/test_program_trace.py`
reads: the program's own spans and device scopes at a size a test holds.

    python3 bench/tools/record_program_trace.py --out chiprun_out/tiny

Inside one `bench.window` span: two rounds of a reduced VGG split
session (three clients, round-robin with the p2p handoff, physical int8
wire, Adam), each in `bench.run_round` and fenced in `bench.fence`, then
two `Batcher.join`s and three `Batcher.step`s of a reduced phi4-mini
split server, each in `bench.join` / `bench.step`.  Everything is
compiled and warmed first.  Writes `<out>/tiny_program.xplane.pb`, the
trace cut to what `bench/lib/trace.py` reads (`shrink`), and
`<out>/tiny_program.hlo.txt`, the round program's compiled text cut to
what the trace's op -> IR step map reads: the `HloModule` line's name,
and every computation but the fused ones (whose instructions never run
as ops of their own), with source locations and backend configs left
out.  Refuses without
a TPU.  `shrink` reads and writes the trace with TensorFlow's XPlane
protobuf, which only this tool needs.
"""
import argparse
import pathlib
import re
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench.lib import harness, program_trace as pt  # noqa: E402
from bench.lib import trace as tr  # noqa: E402

BENCH = harness.BENCH_DIR


def vgg_cell():
    cfg = harness.load_json(BENCH / "configs" / "vgg16-cifar10.json")
    cfg.update(plan=[16, 16, "M", 32, "M"], fc_width=32, hw=8, n_classes=4)
    traffic = dict(harness.load_json(BENCH / "traffic" / "rr100.json"),
                   n_clients=3, per_client=4, pool_rounds=2)
    return cfg, traffic


def serve_cfg():
    cfg = harness.load_json(BENCH / "configs" / "phi4-mini-3.8b.json")
    cfg.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
               head_dim=16, d_ff=128, vocab=256, cut=1, max_batch=2,
               max_len=64)
    return cfg


def trim(text: str) -> str:
    """The compiled text less fused computations, the stack-frame tables,
    source locations and backend configs (a Pallas kernel's body), its
    `HloModule` line cut to the module's name."""
    out, fused, tables = [], False, False
    for line in text.splitlines():
        if line.startswith("HloModule "):
            line = " ".join(line.split()[:2])
        elif line in ("FileNames", "FunctionNames", "FileLocations",
                      "StackFrames"):
            tables = True
        elif line.startswith(("%", "ENTRY")):
            tables = False
            fused = line.startswith("%fused_")
        if not (fused or tables):
            line = re.sub(r", backend_config=\{.*\}$", "", line)
            out.append(re.sub(r' (source_file="[^"]*"|source_(end_)?'
                              r'(line|column)=\d+|stack_frame_id=\d+)',
                              "", line))
    return "\n".join(out) + "\n"


def shrink(src, dst):
    """Keep of a recorded trace what the reduction reads: the device
    planes' `XLA Modules` and `XLA Ops` lines, and on the host planes
    the `bench.*` and `repro.*` spans with their arguments and the
    program launches; drop every other plane, line and event, the
    events' own statistics but the span arguments, and the metadata no
    kept event uses; cut each op's HLO text to what `trace.op_name`
    keeps of it."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    space.ParseFromString(pathlib.Path(src).read_bytes())
    planes = []
    for plane in space.planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            keep_line = {tr.OPS_LINE, tr.MODULES_LINE}.__contains__
            keep_event = lambda name: True  # noqa: E731
        elif plane.name.startswith("/host:") and plane.lines:
            keep_line = lambda name: True  # noqa: E731
            keep_event = lambda name: name.startswith(
                (tr.SPAN_PREFIX, pt.PROGRAM_PREFIX)) or name == tr.LAUNCH
        else:
            continue
        md = plane.event_metadata
        lines = []
        for line in plane.lines:
            if not keep_line(line.name):
                continue
            events = [e for e in line.events
                      if keep_event(md[e.metadata_id].name)]
            if not events:
                continue
            del line.events[:]
            line.events.extend(events)
            for e in line.events:
                if not md[e.metadata_id].name.startswith(
                        pt.PROGRAM_PREFIX):
                    del e.stats[:]
            lines.append(line)
        del plane.lines[:]
        plane.lines.extend(lines)
        used = {e.metadata_id for ln in plane.lines for e in ln.events}
        for k in [k for k in md if k not in used]:
            del md[k]
        for m in md.values():
            del m.stats[:]
            if plane.name.startswith(tr.DEVICE_PREFIX) and " = " in m.name:
                m.name = tr.op_name(m.name)
        del plane.stats[:]
        planes.append(plane)
    del space.planes[:]
    space.planes.extend(planes)
    pathlib.Path(dst).write_bytes(space.SerializeToString())


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    harness.require_chips(1)
    train = harness.load_module(BENCH / "drivers" / "split_train.py",
                                "split_train")
    serve = harness.load_module(BENCH / "drivers" / "split_serve.py",
                                "split_serve")
    vgg = harness.load_module(BENCH / "models" / "vgg16.py", "vgg16")
    phi = harness.load_module(BENCH / "models" / "phi4_mini.py",
                              "phi4_mini")

    cfg, traffic = vgg_cell()
    key_w, key_d = jax.random.split(jax.random.PRNGKey(3))
    pool = train.make_pool(vgg, cfg, traffic, key_d)
    sess = train.build_session(vgg, cfg, traffic)
    sess.init(key_w)
    for batch in pool:
        jax.block_until_ready(sess.run_round(batch))

    scfg = serve_cfg()
    _, bat = serve.build(phi, scfg, key_w)
    rng = np.random.default_rng(0)
    prompts = [jnp.asarray(rng.integers(0, scfg["vocab"], n, dtype=np.int32))
               for n in (8, 16)]
    for p in prompts:                       # compile prefill and scatter
        bat.join(p, 2)
    bat.run()
    bat.finished.clear()

    log_dir = tempfile.mkdtemp(prefix="tiny_program_")
    with jax.profiler.trace(log_dir,
                            profiler_options=harness.trace_options()):
        with jax.profiler.TraceAnnotation("bench.window"):
            for batch in pool:
                with jax.profiler.TraceAnnotation("bench.run_round"):
                    ls = sess.run_round(batch)
                with jax.profiler.TraceAnnotation("bench.fence"):
                    jax.block_until_ready((ls, sess.state))
            for p in prompts:
                with jax.profiler.TraceAnnotation("bench.join"):
                    bat.join(p, 8)
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    bat.step()
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    shrink(tr.xplane_file(log_dir), out / "tiny_program.xplane.pb")
    shutil.rmtree(log_dir, ignore_errors=True)
    text = pt.round_text(sess, pool[0])
    (out / "tiny_program.hlo.txt").write_text(trim(text))
    for f in sorted(out.iterdir()):
        print(f.name, f.stat().st_size)


if __name__ == "__main__":
    main()
