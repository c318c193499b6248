"""Puts the benchmark's own directory on ``sys.path``, as ``bench/run.py``
does when it runs, and names the files the tests share."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
DATA = Path(__file__).resolve().parent / "data"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def load(path):
    return json.loads(Path(path).read_text())


def tiny(**moe):
    """The test-size granite configuration, with the limits of the real
    cells' configuration files, so a test holds the tiny model to the
    same limits the chip runs are held to."""
    conf = load(DATA / "tiny.json")
    conf["moe"].update(moe)
    conf["limits"] = {
        **load(BENCH / "configs/granite-moe-1b-a400m.json")["limits"],
        **load(BENCH / "configs/granite-moe-3b-a800m.json")["limits"],
    }
    return conf


def with_serving(bench):
    """``bench`` with the serving cell and its metrics added. The chat
    cell is held out of ``BENCHMARK.json`` until it can be measured on
    the chip; its harness stays tested under the entries it will have
    (``data/serve_cells.json``)."""
    extra = load(DATA / "serve_cells.json")
    return {k: v + extra[k] if k in extra else v for k, v in bench.items()}
