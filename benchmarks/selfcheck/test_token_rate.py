"""A rate for scored token columns, `tokens_per_s`, through the harness as it
stands: a cell whose rows are token sequences is a matter of files and
entries, with no edit to `run.py`, `harness/` or `drivers/`.

BENCHMARK.json does not have the metric: an end-to-end entry whose `workloads`
list is empty is refused (PR 28's first check), so the entry arrives with its
first cell, in that cell's PR. `RATE_ENTRY` below is that entry as the cell's
PR would write it, and the cell here is built by hand, as `spec.load_cell`
would build one whose name stood in the entry's list. Its subject imports
nothing of the program: rows of int32 token ids with heavy-tailed lengths from
the seed, padded into two length buckets, scored by a small `jax.numpy`
function, compared with the same function in numpy float64. Driven through
`closed_loop.run` on the CPU it shows what the metric counts: the real tokens
of the finite rows of the completed calls, over the window. Never a source of
a device number."""

import time
import types

import numpy as np
import pytest

from benchmarks.harness import check, spec
from benchmarks.harness.check import Compared

BENCH = spec.load_json(spec.ROOT + "/BENCHMARK.json")
RATE = "tokens_per_s"
CELL = "selfcheck.tokens"            # no entry of `workloads`: built by hand
RATE_ENTRY = {"name": RATE, "unit": "tokens/s", "better": "higher", "bound": 0.01,
              "source": "host_clock", "workloads": [CELL]}
SEED = 4294970128                    # over 32 signed bits, as the driver's are
BUCKETS = (64, 512)
ROWS, VOCAB, WIDTH = 48, 1000, 16
SCORE_GAP_LIMIT = 1e-4               # float32 against float64, scores in (-1, 1)


def token_cell(listed: bool = True) -> spec.Cell:
    """The cell `spec.load_cell` would give for a token cell: `end_to_end` as
    BENCHMARK.json has it, with `RATE_ENTRY` beside it where the cell's name
    stands in that entry's `workloads` list (`listed`)."""
    rate = dict(RATE_ENTRY, workloads=[CELL] if listed else ["another.cell"])
    entries = BENCH["end_to_end"] + [rate]
    e2e = [m for m in entries if spec._in_cell(m, CELL, True)]
    traffic = {"driver": "closed_loop", "rate_metric": RATE, "trace_calls": 1}
    return spec.Cell(CELL, 1, "hand-built", {}, "hand-built", traffic, e2e, [])


def _scores(xp, tokens, table, weight):
    """A score a position: the row's embeddings up to it, averaged, against
    its own. Causal, so what is padded after a row's end cannot reach it."""
    x = table[tokens]                                        # [n, L, WIDTH]
    steps = xp.arange(1, tokens.shape[1] + 1, dtype=x.dtype)[:, None]
    return xp.tanh(((xp.cumsum(x, axis=1) / steps) * x * weight).sum(-1))


class TokenSubject:
    """Scores one column of token rows a call, bucket by bucket."""

    def __init__(self, seed: int):
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(seed)
        lengths = np.clip(np.rint(np.exp(rng.normal(3.5, 1.0, ROWS))), 8, 512)
        lengths[:2] = 8, 512                   # both ends, so both buckets
        self.lengths = lengths.astype(np.int64)
        self.rows = [rng.integers(1, VOCAB, n, dtype=np.int32) for n in self.lengths]
        self.table = rng.normal(0, 1, (VOCAB, WIDTH)).astype(np.float32)
        self.weight = rng.normal(0, 1, WIDTH).astype(np.float32)
        self.items_per_call = ROWS
        self.batches = []                      # (row numbers, padded ids)
        lo = 0
        for width in BUCKETS:
            idx = np.flatnonzero((self.lengths > lo) & (self.lengths <= width))
            ids = np.zeros((len(idx), width), np.int32)        # 0 pads
            for k, r in enumerate(idx):
                ids[k, :self.lengths[r]] = self.rows[r]
            self.batches.append((idx, ids))
            lo = width
        table, weight = jnp.asarray(self.table), jnp.asarray(self.weight)
        self._score = jax.jit(lambda ids: _scores(jnp, ids, table, weight))
        self._pick = np.random.default_rng(seed + 1)

    def warm(self) -> None:
        for _, ids in self.batches:
            self._score(ids).block_until_ready()

    def call(self):
        """[(row numbers, padded scores on the host)], a bucket each."""
        return [(idx, np.array(self._score(ids))) for idx, ids in self.batches]

    def _finite(self, out):
        """{row number: whether every real position came back finite}."""
        return {int(r): bool(np.isfinite(scores[k, :self.lengths[r]]).all())
                for idx, scores in out for k, r in enumerate(idx)}

    def work(self, out) -> float:
        # real positions of finite rows: not the pad, not a failed row's
        return float(sum(self.lengths[r] for r, ok in self._finite(out).items() if ok))

    def failed_items(self, out) -> int:
        finite = self._finite(out)
        return ROWS - len(finite) + sum(not ok for ok in finite.values())

    def keep(self, out):
        """A sample of the call's rows from the seed, the longest (row 1)
        always among them; a failed row is failed, not wrong, and is not kept."""
        rows = {int(r): scores[k, :self.lengths[r]].copy()
                for idx, scores in out for k, r in enumerate(idx)}
        finite = self._finite(out)
        sample = {1, *self._pick.choice(ROWS, 8, replace=False).tolist()}
        return {r: rows[r] for r in sample if finite.get(r)}

    def counters(self):
        return {}

    def free(self) -> None:
        self._score = None

    def check(self, kept):
        table, weight = self.table.astype(np.float64), self.weight.astype(np.float64)
        gap = 0.0
        for call in kept:
            for r, got in call.items():
                want = _scores(np, self.rows[r][None, :], table, weight)[0]
                gap = max(gap, float(np.abs(got - want).max()))
        return [Compared("score_gap", gap, SCORE_GAP_LIMIT)]


def builder(fault=None, pad_is_work=False):
    """A builder of the subject, which it keeps as `.subject` for the test to
    read; `fault(subject, out, n)` alters the n-th call's output where it is
    produced (n from 1), `pad_is_work` plants the counting fault."""
    made = types.SimpleNamespace(subject=None)

    def build(config, traffic, seed, chips):
        subject = made.subject = TokenSubject(seed)
        if fault is not None:
            call, state = subject.call, {"n": 0}

            def faulty():
                state["n"] += 1
                return fault(subject, call(), state["n"])

            subject.call = faulty
        if pad_is_work:
            subject.work = lambda out: float(sum(s.size for _, s in out))
        return subject

    made.build = build
    return made


def _exactly(tokens):
    return pytest.approx(tokens, rel=1e-9)      # a token more or less shows


def _run(cell, build, seconds=0.3):
    import jax
    from benchmarks.drivers import closed_loop

    res = closed_loop.run(cell, build, jax.devices()[:1], SEED, seconds, False,
                          time.perf_counter())
    res["tokens"] = res["metrics"][RATE]["value"] * res["window_s"]
    res["real_tokens"] = res["calls"] * int(build.subject.lengths.sum())
    return res


def test_an_untraced_line_is_the_rate_and_setup_and_counts_real_tokens():
    res = _run(token_cell(), builder())
    assert set(res["metrics"]) == {RATE, "setup_s"}
    assert res["metrics"][RATE]["unit"] == "tokens/s"
    assert res["calls"] >= 2 and res["failed"] == 0
    assert res["attempted"] == res["calls"] * ROWS           # rows, not tokens
    assert res["compiles_in_window"] == 0
    assert res["tokens"] == _exactly(res["real_tokens"])
    assert check.verdict(res["compared"])


def test_padding_counted_as_work_reads_high():
    planted = builder(pad_is_work=True)
    res = _run(token_cell(), planted)
    padded = sum(ids.size for _, ids in planted.subject.batches)
    assert res["tokens"] == _exactly(res["calls"] * padded)
    assert res["tokens"] > 1.5 * res["real_tokens"]   # pad positions as work


def test_a_raised_call_fails_its_rows_and_gives_none_of_its_tokens():
    def refuse_second(subject, out, n):
        if n == 2:
            raise RuntimeError("refused")
        return out

    res = _run(token_cell(), builder(refuse_second))
    assert res["failed"] == ROWS
    assert res["attempted"] == (res["calls"] + 1) * ROWS
    assert res["tokens"] == _exactly(res["real_tokens"])
    assert check.verdict(res["compared"])


def test_a_row_not_finite_fails_alone_and_takes_its_own_tokens_out():
    def nan_in_row_1(subject, out, n):
        if n == 1:
            (idx, scores), = [b for b in out if 1 in b[0]]
            scores[list(idx).index(1), 100] = np.nan     # row 1 has 512 positions
        return out

    res = _run(token_cell(), builder(nan_in_row_1))
    assert res["failed"] == 1 and res["attempted"] == res["calls"] * ROWS
    assert res["tokens"] == _exactly(res["real_tokens"] - 512)
    assert check.verdict(res["compared"])


def test_a_nan_in_the_padding_fails_nothing():
    def nan_in_pad(subject, out, n):
        idx, scores = out[0]                    # the bucket of 64
        scores[subject.lengths[idx] < 64, -1] = np.nan
        return out

    res = _run(token_cell(), builder(nan_in_pad))
    assert res["failed"] == 0
    assert res["tokens"] == _exactly(res["real_tokens"])


def test_an_altered_score_is_not_correct():
    def shifted(subject, out, n):
        return [(idx, np.roll(scores, 1, axis=1)) for idx, scores in out]

    res = _run(token_cell(), builder(shifted))
    assert res["failed"] == 0 and not check.verdict(res["compared"])


def test_why_no_token_cell_could_be_added_before():
    """A cell whose `rate_metric` is not among its `end_to_end` (BENCHMARK.json
    as it is, without `tokens_per_s`, or a cell that the entry's `workloads`
    list leaves out) gets through its window and then has no unit to report
    the rate under."""
    cell = token_cell(listed=False)
    assert {m["name"] for m in cell.end_to_end} == {"setup_s"}
    with pytest.raises(KeyError, match=RATE):
        _run(cell, builder())


def test_every_listed_metric_of_the_benchmark_names_a_cell():
    """The rule PR 28's first check refused it by: a `workloads` list, where a
    metric has one, names at least one cell. So `tokens_per_s` cannot wait in
    BENCHMARK.json for a cell, and is not there until one reports it."""
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if "workloads" in m:
            assert m["workloads"] and set(m["workloads"]) <= cells, m["name"]
