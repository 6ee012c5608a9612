"""Record the small trace that ``test_devtrace.py`` reads, on a TPU.

    python3 benchmarks/chip/tests/record_trace.py OUT_DIR

Runs the tiny cell (``tiny.py``) through ``run.run_cell`` with the trace
on, one round in the window, and writes to ``OUT_DIR`` what
``tests/data/small_trace/`` holds: ``window.xplane.pb.gz``,
``window.hlo.txt.gz`` and ``result.json``.  The checkout's absolute path,
which both files carry in their source locations, is replaced by a
placeholder of the same length, so the protobuf stays valid.
"""
from __future__ import annotations

import glob
import gzip
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from tiny import RECORDED_SEED, tiny_cell  # noqa: E402


def scrub(data: bytes) -> bytes:
    prefix = (str(run.HERE.parents[1]) + "/").encode()
    return data.replace(prefix, b"/" + b"_" * (len(prefix) - 2) + b"/")


def main(out: Path) -> dict:
    raw = out / "raw"
    res = run.run_cell(tiny_cell(), seed=RECORDED_SEED, seconds=0.0,
                       trace=True, trace_dir=str(raw))
    pb = glob.glob(str(raw / "**" / "*.xplane.pb"), recursive=True)[0]
    for src, dst in ((pb, "window.xplane.pb.gz"),
                     (raw / "window.hlo.txt", "window.hlo.txt.gz")):
        with gzip.open(out / dst, "wb", compresslevel=9) as f:
            f.write(scrub(Path(src).read_bytes()))
    (out / "result.json").write_text(json.dumps(res, indent=1))
    return res


if __name__ == "__main__":
    out = Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    print(json.dumps(main(out)))
