"""The readers of the program's span records, on hand-made records: the
four per-layer metrics through their ``perfbench/metrics/*.json`` files,
the information lines, and nothing to read where the program keeps no
records."""
import pytest

import pb_tiny  # noqa: F401  (puts perfbench/ on sys.path)
from pb import cells

MS = 1_000_000      # ns


def _tick(ids, tick, t0, parts, **root):
    """One ``serve.step`` record at ``t0`` ms with children laid end to
    end after 0.1 ms of the tick's own work: ``parts`` is
    ``[(label, ms, [sub-parts])]``."""
    recs = []

    def lay(label, ms, sub, parent, at):
        name, _, tag = label.partition(":")
        rec = {"span": name, "id": next(ids), "parent": parent,
               "tick": tick, "t0_ns": int(at * MS),
               "t1_ns": int((at + ms) * MS)}
        if name == "dispatch":
            rec["kind"] = tag
        elif name == "serve.fetch":
            rec["what"] = tag
        recs.append(rec)
        inner = at
        for sl, sms, ssub in sub:
            lay(sl, sms, ssub, rec["id"], inner)
            inner += sms
        return rec
    total = 0.1 + sum(ms for _, ms, _ in parts)
    rec = lay("serve.step", total, [], None, t0)
    rec.update(root)
    at = t0 + 0.1
    for label, ms, sub in parts:
        lay(label, ms, sub, rec["id"], at)
        at += ms
    return recs, {"t0": (t0 - 0.05) / 1e3, "t1": (t0 + total + 0.05) / 1e3}


def _decode(fetch_ms, commit_ms=0.4):
    return [("serve.ensure_blocks", 0.1, []), ("serve.pack", 0.2, []),
            ("dispatch:decode_step", 1.0, []),
            ("serve.fetch:tokens", fetch_ms, []),
            ("serve.commit", commit_ms, [])]


@pytest.fixture
def serve_ctx(monkeypatch):
    """Five ticks: a warm-up tick before the window, three decode ticks
    (one of them carrying a prefill chunk) and a tick that only
    prefills."""
    from apex_tpu.observe import spans
    ids = iter(range(1, 1000))
    prefill = ("serve.prefill_chunk", 64.0,
               [("dispatch:prefill_step", 1.0, []),
                ("serve.fetch:first_token", 62.0, [])])
    plan = [(1, 1000.0, _decode(500.0), dict(decode_batch=16,
                                             prefill_rid=None)),
            (2, 2000.0, _decode(88.0), dict(decode_batch=16,
                                            prefill_rid=None)),
            (3, 2100.0, [prefill] + _decode(89.0),
             dict(decode_batch=15, prefill_rid="r9")),
            (4, 2300.0, _decode(90.0, commit_ms=1.4),
             dict(decode_batch=16, prefill_rid=None)),
            (5, 2400.0, [prefill], dict(decode_batch=0,
                                        prefill_rid="r10"))]
    records, ticks = [], []
    for tick, t0, parts, root in plan:
        recs, tk = _tick(ids, tick, t0, parts, **root)
        records += recs
        tk["dispatches"] = [r["kind"] for r in recs
                            if r["span"] == "dispatch"]
        ticks.append(tk)
    records.sort(key=lambda r: r["t0_ns"])
    monkeypatch.setattr(spans, "recorded", lambda since_ns=None: records,
                        raising=False)
    # the window holds ticks 2..5; tick 1 was warm-up
    return {"counters": {"ticks": ticks[1:]}}


def _read(name, ctx):
    reader, kw = cells.metric_reader(name)
    return reader(ctx, **kw)


def test_decode_tick_metrics_split_the_tick_at_the_fetch(serve_ctx, capsys):
    # decode ticks of the window: 2, 3, 4; their fetches 88, 89 + 62, 90
    assert _read("decode_fetch_wait_ms", serve_ctx) == pytest.approx(90.0)
    # the rest of each: 1.8, 1.8 + 2.0, 2.8 ms
    assert _read("decode_tick_host_ms", serve_ctx) == pytest.approx(2.8)
    out = capsys.readouterr().out.splitlines()
    # read once, reported once, whatever the number of readers
    assert len([ln for ln in out if "longest tick" in ln]) == 1
    (per_name,) = [ln for ln in out if "serve.fetch.tokens:" in ln]
    assert "4 in the run" in per_name and "max 500.000 ms" in per_name
    (self_line,) = [ln for ln in out if "self time" in ln]
    assert "p50 0.100" in self_line and "3 decode ticks" in self_line
    (longest,) = [ln for ln in out if "longest tick" in ln]
    # since the window opened: not the 500 ms tick of the warm-up
    assert "longest tick of 4 since the window opened: tick 3, " \
           "decode_batch 15, prefill_rid r9: serve.step 154.800 " \
           "(serve.prefill_chunk 64.000 (dispatch.prefill_step 1.000, " \
           "serve.fetch.first_token 62.000, self 1.000), " \
           "serve.ensure_blocks 0.100" in longest
    assert longest.endswith("serve.commit 0.400, self 0.100)")


def test_an_untraced_run_names_where_its_longest_tick_stood(serve_ctx,
                                                            monkeypatch):
    spans = cells._module_at(cells.REPO, "readers", "spans")
    ticks = serve_ctx["counters"]["ticks"]
    line = spans.longest_tick_line(ticks)
    assert line.startswith("serve.step 154.800 (serve.prefill_chunk 64.000 ")
    assert "serve.fetch.tokens 89.000" in line
    assert spans.longest_tick_line([]) is None
    # the ring has lost the tick, or the program keeps no records
    assert spans.longest_tick_line(
        [{"t0": 9.0, "t1": 9.5, "dispatches": []}]) is None
    from apex_tpu.observe import spans as program
    monkeypatch.delattr(program, "recorded")
    assert spans.longest_tick_line(ticks) is None


def test_train_metrics_are_medians_of_the_windows_last_steps(monkeypatch):
    from apex_tpu.observe import spans
    records = []
    for i, (disp, wait) in enumerate([(400.0, 30.0), (3.0, 0.02),
                                      (5.0, 0.04), (4.0, 0.03)]):
        t = 1000.0 * i
        records.append({"span": "data.wait", "id": 3 * i + 1, "parent": None,
                        "t0_ns": int(t * MS), "t1_ns": int((t + wait) * MS)})
        records.append({"span": "dispatch", "kind": "train_step",
                        "id": 3 * i + 2, "parent": None, "step": i + 1,
                        "t0_ns": int((t + 50) * MS),
                        "t1_ns": int((t + 50 + disp) * MS)})
        records.append({"span": "dispatch", "kind": "decode_step",
                        "id": 3 * i + 3, "parent": None,
                        "t0_ns": int((t + 500) * MS),
                        "t1_ns": int((t + 600) * MS)})
    monkeypatch.setattr(spans, "recorded", lambda since_ns=None: records,
                        raising=False)
    ctx = {"counters": {"steps": 3}}        # the first step was set-up
    assert _read("train_dispatch_host_ms", ctx) == pytest.approx(4.0)
    assert _read("train_input_wait_ms", ctx) == pytest.approx(0.03)
    # fewer records than steps: the ring lost some, nothing is read
    assert _read("train_dispatch_host_ms",
                 {"counters": {"steps": 5}}) is None


@pytest.mark.parametrize("name", ["decode_tick_host_ms",
                                  "decode_fetch_wait_ms",
                                  "train_dispatch_host_ms",
                                  "train_input_wait_ms"])
@pytest.mark.parametrize("program", ["keeps_no_records", "has_none_yet"])
def test_nothing_to_read_is_none(monkeypatch, name, program):
    """A program without ``spans.recorded`` (a parent commit) and one
    that recorded nothing both leave the metric out, and do not raise."""
    from apex_tpu.observe import spans
    if program == "keeps_no_records":
        monkeypatch.delattr(spans, "recorded")
    else:
        monkeypatch.setattr(spans, "recorded", lambda since_ns=None: [])
    ctx = {"counters": {"steps": 4, "ticks": [
        {"t0": 1.0, "t1": 1.1, "dispatches": ["decode_step"]}]}}
    assert _read(name, ctx) is None


def test_the_four_metrics_are_declared_with_their_cells():
    bench = cells.load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, cell, moves in [
            ("decode_tick_host_ms", "gpt2m-serve-decode",
             "serve_tokens_per_s"),
            ("decode_fetch_wait_ms", "gpt2m-serve-decode",
             "serve_tokens_per_s"),
            ("train_dispatch_host_ms", "gpt2s-train", "train_tokens_per_s"),
            ("train_input_wait_ms", "gpt2s-train", "train_tokens_per_s")]:
        m = declared[name]
        assert m["workloads"] == [cell] and m["moves"] == moves
        assert m["unit"] == "ms" and m["better"] == "lower"
        assert m in cells.Cell(cell).per_layer
        assert not name.endswith(("_device_ms", "_mfu", "_roofline",
                                  "_idle_share"))
