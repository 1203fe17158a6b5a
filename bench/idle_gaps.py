"""The device's longest idle gaps in a traced run, named by the program's
own spans.

    python bench/idle_gaps.py [<profile dir>]

Reads the profile a ``--trace 1`` run of ``bench/run.py`` left (under
``bench/.cache/trace`` by default) and prints, as one JSON line, the ten
longest gaps of the window in which the device ran no op: each
``[label, seconds]``, the label being the shortest ``acan.`` (program) or
``bench.`` (benchmark) host span that covers the gap's midpoint. The
result line's ``breakdown.idle_gaps`` names them by ``bench.`` spans
alone.
"""

from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    sys.path.insert(0, os.path.join(BENCH, "lib"))
    import devtrace
    import spans

    log_dir = args[0] if args else os.path.join(BENCH, ".cache", "trace")
    gaps = spans.idle_gaps(devtrace.read_xplane(log_dir))
    if gaps is None:
        print(f"idle_gaps.py: no window with device ops under {log_dir}",
              file=sys.stderr)
        return 1
    print(json.dumps(gaps))
    return 0


if __name__ == "__main__":
    sys.exit(main())
