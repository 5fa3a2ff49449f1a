"""The per-layer metrics that read the program's own spans
(``repro.serve.spans``): the names admission gives its spans reach the
harness's recorder in a small run and each is read by a metric, the
names only migration gives are read by none, and each reader gives its
value on hand-made spans and nothing without them."""

import re

import pytest

from chipbench import bench
from chipbench.executor import Spans
from conftest import small_cell

READERS = ["graft_ms", "graft_mb", "step_host_ms", "prefill_rows_used"]
# the spans an admission records, and those only a migration records
# (its host graft's parts): no cell migrates, so no metric reads them
ADMISSION = {"serve.iteration", "serve.prefill", "serve.graft",
             "serve.decode.wait"}
MIGRATION = {"serve.graft.fetch", "serve.graft.merge", "serve.graft.place"}


def graft_rows(t, secs, rows):
    """One admission at ``t``: a 0.1-s prefill of ``rows`` real rows of
    8, a graft of ``secs`` moving 3 MB out and 2 MB back, and a 0.06-s
    decode wait, inside one iteration."""
    g = t + 0.1 + secs
    return [("serve.iteration", t, g + 0.2, None),
            ("serve.prefill", t, t + 0.1, {"rows": rows, "padded_rows": 8}),
            ("serve.graft", t + 0.1, g, {"d2h_bytes": 3_000_000,
                                         "h2d_bytes": 2_000_000}),
            ("serve.decode.wait", g + 0.1, g + 0.16, None)]


def steady_rows(t, secs, wait):
    """An iteration of ``secs`` that only decodes, ``wait`` of it spent
    waiting for the step."""
    return [("serve.iteration", t, t + secs, None),
            ("serve.decode.wait", t + 0.001, t + 0.001 + wait, None)]


def ctx(rows):
    spans = Spans()
    spans.rows = sorted(rows, key=lambda r: r[1])
    win = bench.Window(preroll_s=5, seconds=10, nfill=8)
    win.t_open, win.t_stop = 10.0, 20.5
    return bench.Ctx(None, [], win, spans, None, None, 0.0, 8)


def program_ctx():
    rows = (graft_rows(10.0, 0.3, 1) + graft_rows(14.0, 0.5, 2)
            + steady_rows(11.0, 0.05, 0.03) + steady_rows(12.0, 0.1, 0.05)
            # an idle pass: no decode, not a steady step
            + [("serve.iteration", 13.0, 13.01, None)]
            # after the close: left out
            + graft_rows(21.0, 0.9, 8) + steady_rows(23.0, 0.5, 0.01))
    return ctx(rows)


def harness_ctx():
    """What a program without spans of its own leaves: the harness's."""
    rows = [("executor.prefill", 10.0, 14.0, [128]),
            ("executor.decode", 14.0, 14.04, [129])]
    return ctx(rows)


@pytest.mark.parametrize("name,want", [
    ("graft_ms", 400.0),            # (300 + 500) / 2
    ("graft_mb", 5.0),              # 3 MB + 2 MB a graft
    ("step_host_ms", 35.0),         # (50 - 30 + 100 - 50) / 2
    ("prefill_rows_used", 18.75),   # (1 + 2) / (8 + 8)
])
def test_reader_on_hand_made_spans(name, want):
    assert bench.load_metric(name)(program_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_the_program_spans(name):
    assert bench.load_metric(name)(harness_ctx()) is None


def test_every_span_the_program_names_reaches_the_recorder(
        no_compile_cache):
    # the names the program's source gives its spans
    src = (bench.ROOT / "src" / "repro").rglob("*.py")
    emitted = {m for p in src
               for m in re.findall(r'span\("(serve\.[a-z_.]+)"',
                                   p.read_text())}
    # and the ones the readers read
    read = {m for p in (bench.HERE / "metrics").glob("*.py")
            for m in re.findall(r'"(serve\.[a-z_.]+)"', p.read_text())}
    seen = {}
    bench.run("small", 2 ** 33 + 5, 3.0, False,
              cell=small_cell("deepseek-7b-pp2", "longgen"),
              on_executor=lambda ex: seen.setdefault("ex", ex),
              require_tpu=False, log=lambda *a: None)
    recorded = {r[0] for r in seen["ex"].spans.rows}
    assert emitted == ADMISSION | MIGRATION
    assert read == ADMISSION
    assert ADMISSION <= recorded and not MIGRATION & recorded
