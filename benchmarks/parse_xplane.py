"""Per-op aggregation of a jax.profiler xplane capture.

Usage: ``python benchmarks/parse_xplane.py <trace>/plugins/profile/*/\
*.xplane.pb`` — prints, per TPU device plane, the total duration and
event count of every HLO op, most expensive first. This is how the
round-4 roofline attribution (PERF.md 'Earlier records') located
the activation-stream fusions that dominate the income round.
"""
import sys, collections
from tensorflow.tsl.profiler.protobuf import xplane_pb2
for path in sys.argv[1:]:
  print(f"=== file: {path}")
  xs = xplane_pb2.XSpace()
  xs.ParseFromString(open(path, "rb").read())
  for plane in xs.planes:
    print("== plane:", plane.name)
    if "TPU" not in plane.name and "device" not in plane.name.lower():
        continue
    ev_meta = {i: m.name for i, m in plane.event_metadata.items()}
    agg = collections.Counter()
    cnt = collections.Counter()
    for line in plane.lines:
        for ev in line.events:
            name = ev_meta.get(ev.metadata_id, str(ev.metadata_id))
            agg[name] += ev.duration_ps
            cnt[name] += 1
    total = sum(agg.values())
    print(f"  line events total {total/1e12*1e6:.1f} us (all lines)")
    for name, ps in agg.most_common(25):
        print(f"  {ps/1e6:10.1f} us  n={cnt[name]:<7} {name[:90]}")
