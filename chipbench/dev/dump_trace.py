"""Look at one trace by hand: planes, lines, a few events with their
stats, and the operations that took most time.

    python chipbench/dev/dump_trace.py <file.xplane.pb> [out.txt]
"""
import collections
import sys


def main(path, out=None):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    w = open(out, "w") if out else sys.stdout
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines", file=w)
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            interesting = plane.name.startswith("/device:") or any(
                e.name.startswith("chipbench/") for e in evs[:2000])
            print(f"  LINE {line.name!r}: {len(evs)} events"
                  + ("" if interesting else " (skipped)"), file=w)
            if not interesting:
                continue
            for e in evs[:4]:
                print(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns}"
                      f" stats={dict(e.stats)}", file=w)
            tot = collections.Counter()
            cnt = collections.Counter()
            for e in evs:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
            print("    -- top by total duration:", file=w)
            for name, ns in tot.most_common(25):
                print(f"    {ns / 1e6:10.3f} ms  x{cnt[name]:<6} {name}",
                      file=w)
    if out:
        w.close()


if __name__ == "__main__":
    main(*sys.argv[1:3])
