#!/usr/bin/env python3
"""Find where the window's host stalls fall: run one cell with a thread that
samples the main thread's Python stack every 5 ms.

    python3 bench/stall_sampler.py --workload <cell> --seed <n> --seconds <s> --trace 0

The arguments are ``bench/run.py``'s, and the run is the same.  At exit it
prints to stderr, as ``[stall]`` lines, the longest spells (over 20 ms)
in which the main thread's stack stayed put inside a driver's window loop,
each with its innermost frames.  A spell inside a call into the device or
a transfer points at the runtime; one on a line of the engine's Python
points at that line.
"""

import atexit
import runpy
import sys
import threading
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
LOOPS = ("open_loop.py", "zo_train.py")


def frames(fr, n: int = 30) -> tuple:
    out = []
    while fr is not None and len(out) < n:
        code = fr.f_code
        out.append(f"{Path(code.co_filename).name}:{fr.f_lineno}:"
                   f"{code.co_name}")
        fr = fr.f_back
    return tuple(out)


def main() -> None:
    main_id = threading.main_thread().ident
    spells, last = [], {"stack": None, "t": 0.0}

    def in_window(stack) -> bool:
        return any(f.split(":")[0] in LOOPS and f.endswith((":run", ":loop"))
                   for f in stack)

    def sample():
        while True:
            time.sleep(0.005)
            fr = sys._current_frames().get(main_id)
            if fr is None:
                continue
            stack, now = frames(fr), time.perf_counter()
            if stack != last["stack"]:
                if (last["stack"] is not None and now - last["t"] > 0.02
                        and in_window(last["stack"])):
                    spells.append((now - last["t"], last["stack"]))
                last["stack"], last["t"] = stack, now

    def report():
        for dt, stack in sorted(spells, key=lambda s: -s[0])[:12]:
            print(f"[stall] {dt * 1e3:.1f} ms at {' < '.join(stack[:7])}",
                  file=sys.stderr)

    threading.Thread(target=sample, daemon=True).start()
    atexit.register(report)
    sys.argv = [str(BENCH / "run.py")] + sys.argv[1:]
    runpy.run_path(str(BENCH / "run.py"), run_name="__main__")


if __name__ == "__main__":
    main()
