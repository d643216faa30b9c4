#!/usr/bin/env python3
"""Device seconds by program scope and by kernel, from a raw profiler trace.

    python3 tools/trace_scopes.py <trace.xplane.pb> [--top N] [--json]

Every op of a compiled shifu_tpu program carries its path of
`jax.named_scope`s as `op_name` (`jit(_gbt_rounds)/while/body/.../route/
jit(take_along_axis)/gather`), and a profiler trace keeps it as the `tf_op`
stat of each device event's *metadata* — which `jax.profiler.ProfileData`
does not list, so the file is read as the protobuf it is. For each TPU
plane's `XLA Ops` line this prints the self seconds (a `while` holds its
body's events) by path of registered scopes
(`shifu_tpu.obs.trace.device_scopes`: `forward_loss/layer1`), by innermost
scope, by Pallas kernel name (the
`pallas_call`'s `name=`, which is also the compiled instruction's name),
the share in ops of no scope, and the largest ops with the scope of each.

A program read back from a persistent compile cache that an older build
filled can carry no scopes at all: jax leaves debug metadata out of the
cache key. Read scopes from a run whose cache started empty.

Exit 2 where no `xplane_pb2` can be imported (xprof,
tensorboard-plugin-profile and tensorflow each may ship one).
"""

import argparse
import importlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce  # noqa: E402
from shifu_tpu.obs.trace import device_scopes  # noqa: E402

XPLANE_MODULES = ("xprof.protobuf.xplane_pb2",
                  "tensorboard_plugin_profile.protobuf.xplane_pb2",
                  "tsl.profiler.protobuf.xplane_pb2",
                  "tensorflow.tsl.profiler.protobuf.xplane_pb2")
KERNEL = re.compile(r"(?:^|/)(shifu_\w+)/pallas_call$")
UNSCOPED = "(no scope)"


def load_xplane_pb2():
    for name in XPLANE_MODULES:
        try:
            return importlib.import_module(name)
        except ImportError:
            continue
    return None


def device_events(space):
    """{plane name: [(Event with self time, op_name)]} of the TPU planes."""
    out = {}
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        stat_ids = {i for i, m in plane.stat_metadata.items()
                    if m.name == "tf_op"}
        op_names = {}
        for i, md in plane.event_metadata.items():
            tf_op = next((s.str_value for s in md.stats
                          if s.metadata_id in stat_ids), "")
            op_names[i] = (md.name, tf_op.rsplit(":", 1)[0])
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            pairs = []
            for ev in line.events:
                text, op_name = op_names.get(ev.metadata_id, ("?", ""))
                start = ev.offset_ps / 1e12
                pairs.append((trace_reduce.Event(
                    trace_reduce.short_name(text), start,
                    start + ev.duration_ps / 1e12, detail=text), op_name))
            trace_reduce.set_self_times([e for e, _ in pairs])
            out[plane.name] = pairs
    return out


def account(events):
    """Self seconds by path of scopes, by innermost scope and by kernel,
    and the ops themselves."""
    scopes, innermost, kernels, ops = {}, {}, {}, {}
    for e, op_name in events:
        path = device_scopes(op_name)
        scope = "/".join(path) or UNSCOPED
        scopes[scope] = scopes.get(scope, 0.0) + e.self_s
        inner = path[-1] if path else UNSCOPED
        innermost[inner] = innermost.get(inner, 0.0) + e.self_s
        m = KERNEL.search(op_name)
        if m and trace_reduce.PALLAS_CALL in e.detail:
            kernels[m.group(1)] = kernels.get(m.group(1), 0.0) + e.self_s
        key = (e.name, scope, op_name)
        ops[key] = ops.get(key, 0.0) + e.self_s
    busy = sum(scopes.values())
    most_first = lambda d: dict(sorted(d.items(),  # noqa: E731
                                       key=lambda kv: -kv[1]))
    return {"busy_s": busy, "scopes": most_first(scopes),
            "innermost": most_first(innermost),
            "kernels": most_first(kernels),
            "unscoped_share": scopes.get(UNSCOPED, 0.0) / busy if busy else 0,
            "ops": [[n, s, o, v] for (n, s, o), v in
                    sorted(ops.items(), key=lambda kv: -kv[1])]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a .xplane.pb written by jax.profiler")
    ap.add_argument("--top", type=int, default=12,
                    help="how many of the largest ops to list")
    ap.add_argument("--json", action="store_true",
                    help="print one JSON object instead of tables")
    args = ap.parse_args(argv)
    xplane_pb2 = load_xplane_pb2()
    if xplane_pb2 is None:
        print("trace_scopes: no xplane_pb2 can be imported here (tried "
              + ", ".join(XPLANE_MODULES) + "); the op_name of a device "
              "event is a stat of its metadata, which only the protobuf "
              "shows", file=sys.stderr)
        return 2
    space = xplane_pb2.XSpace()
    with open(args.trace, "rb") as f:
        space.ParseFromString(f.read())
    planes = device_events(space)
    if not planes:
        print(f"trace_scopes: {args.trace} holds no TPU plane with an "
              f"'{trace_reduce.OPS_LINE}' line", file=sys.stderr)
        return 1
    result = {name: account(events) for name, events in planes.items()}
    if args.json:
        for acc in result.values():
            acc["ops"] = acc["ops"][:args.top]
        print(json.dumps(result))
        return 0
    for name, acc in result.items():
        busy = acc["busy_s"]
        print(f"{name}: {busy:.3f} s of device self time, "
              f"{100 * (1 - acc['unscoped_share']):.1f}% under a scope")
        for title, table in (("scope", acc["scopes"]),
                             ("inner", acc["innermost"]),
                             ("kernel", acc["kernels"])):
            for key, s in table.items():
                print(f"  {title:6s} {key:32s} {s:10.4f} s "
                      f"{100 * s / busy:6.2f}%")
        for op, scope, op_name, s in acc["ops"][:args.top]:
            print(f"  op     {op:32s} {s:10.4f} s {100 * s / busy:6.2f}%  "
                  f"{scope}  [{op_name}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
