"""Scheduler guard rails: Result validation, straggler recovery, and
job-level checkpoint/resume.

The reference's epoch machinery detects dead *connections* only
(``lsp/params.go:16-19``); these tests pin the framework's additional
guarantees: a lying miner cannot corrupt a job's answer, a live-but-hung
miner cannot stall a job forever, and a restarted fleet resumes a job
without re-sweeping completed sub-ranges.
"""

from bitcoin_miner_tpu.apps.scheduler import Scheduler, _merge_intervals
from bitcoin_miner_tpu.bitcoin.hash import hash_nonce, min_hash_range
from bitcoin_miner_tpu.bitcoin.message import MsgType
from bitcoin_miner_tpu.utils.metrics import METRICS

DATA = "cmu440"


def honest(data, lo, hi):
    """What a correct miner replies for chunk [lo, hi]."""
    return min_hash_range(data, lo, hi)


def requests(actions):
    return [(cid, m) for cid, m in actions if m.type == MsgType.REQUEST]


def results(actions):
    return [(cid, m) for cid, m in actions if m.type == MsgType.RESULT]


class TestResultValidation:
    def test_honest_result_accepted(self):
        METRICS.reset()
        s = Scheduler(min_chunk=1000)
        s.miner_joined(1)
        s.client_request(10, DATA, 0, 99)
        h, n = honest(DATA, 0, 99)
        final = results(s.result(1, h, n))
        assert final[0][1].hash == h and final[0][1].nonce == n
        assert METRICS.get("sched.results_rejected") == 0

    def test_lying_hash_rejected_and_chunk_requeued(self):
        METRICS.reset()
        s = Scheduler(min_chunk=1000)
        s.miner_joined(1)
        s.miner_joined(2)
        s.client_request(10, DATA, 0, 99)
        liar = next(m for m in s.miners.values() if m.job is not None).conn_id
        other = 3 - liar
        # Bogus hash: valid nonce, wrong value.
        acts = s.result(liar, hash_=12345, nonce=7)
        assert results(acts) == []  # job must NOT complete on a lie
        assert METRICS.get("sched.results_rejected") == 1
        # Chunk went straight to the idle honest miner.
        req = requests(acts)
        assert req and req[0][0] == other
        h, n = honest(DATA, 0, 99)
        final = results(s.result(other, h, n))
        assert (final[0][1].hash, final[0][1].nonce) == (h, n)

    def test_out_of_range_nonce_rejected(self):
        METRICS.reset()
        s = Scheduler(min_chunk=1000)
        s.miner_joined(1)
        s.client_request(10, DATA, 0, 99)
        # Correct hash for a nonce outside the assigned interval.
        n = 500
        acts = s.result(1, hash_nonce(DATA, n), n)
        assert results(acts) == []
        assert METRICS.get("sched.results_rejected") == 1

    def test_liar_evicted_after_max_rejects(self):
        METRICS.reset()
        s = Scheduler(min_chunk=1000, max_rejects=2)
        s.miner_joined(1)
        s.client_request(10, DATA, 0, 99)
        s.result(1, 1, 1)  # strike 1 (chunk re-queued, re-assigned to 1)
        assert 1 in s.miners
        s.result(1, 2, 2)  # strike 2 -> evicted
        assert 1 not in s.miners
        assert METRICS.get("sched.miners_evicted") == 1
        assert s.drain_evictions() == [1]  # shell is told to close the conn
        assert s.drain_evictions() == []  # drained once
        # A re-Join on the same conn must NOT reset the strike count.
        assert s.miner_joined(1) == []
        assert 1 not in s.miners
        # An honest replacement still completes the job.
        acts = s.miner_joined(2)
        assert requests(acts)[0][1].lower == 0
        h, n = honest(DATA, 0, 99)
        assert results(s.result(2, h, n))


class TestStragglerRecovery:
    def test_hung_miner_chunk_requeued_after_timeout(self):
        METRICS.reset()
        s = Scheduler(min_chunk=100, straggler_min_seconds=10.0)
        s.miner_joined(1, now=0.0)
        s.miner_joined(2, now=0.0)
        s.client_request(10, DATA, 0, 99, now=0.0)  # one chunk, one busy miner
        hung = next(m for m in s.miners.values() if m.job is not None).conn_id
        other = 3 - hung
        assert s.tick(5.0) == []  # before the deadline: nothing
        acts = s.tick(11.0)  # past straggler_min_seconds
        req = requests(acts)
        assert req and req[0][0] == other  # idle peer picked the chunk up
        assert METRICS.get("sched.chunks_straggler_requeued") == 1
        # The fast peer's Result completes the job.
        h, n = honest(DATA, 0, 99)
        final = results(s.result(other, h, n, now=11.5))
        assert (final[0][1].hash, final[0][1].nonce) == (h, n)
        # The hung miner's late duplicate is folded harmlessly and idles it.
        assert s.result(hung, h, n, now=60.0) == []
        assert s.miners[hung].job is None

    def test_rate_based_deadline(self):
        # A miner with a known fast rate gets a deadline ~4x its expected
        # chunk duration, not the 10s floor... unless the floor is larger.
        # depth=1 so exactly one assignment's deadline is under test.
        s = Scheduler(
            min_chunk=100,
            straggler_factor=4.0,
            straggler_min_seconds=0.5,
            target_chunk_seconds=1.0,
            pipeline_depth=1,
        )
        s.miner_joined(1, now=0.0)
        s.client_request(10, DATA, 0, 10**6, now=0.0)
        h, n = honest(DATA, 0, 99)
        s.result(1, h, n, now=0.001)  # 100 nonces/ms -> rate 1e5/s
        # Next chunk targets 1s of work; deadline = 4x expected = ~4s.
        assert s.tick(2.0) == []  # not yet
        assert s.miners[1].timed_out is False
        s.tick(5.0)
        assert s.miners[1].timed_out is True

    def test_straggler_result_arrives_first_withdraws_duplicate(self):
        s = Scheduler(min_chunk=100, straggler_min_seconds=1.0)
        s.miner_joined(1, now=0.0)
        s.client_request(10, DATA, 0, 99, now=0.0)
        s.tick(2.0)  # re-queued, but no peer to take it
        job = s.jobs[10]
        assert list(job.pending) == [(0, 99)]
        h, n = honest(DATA, 0, 99)
        final = results(s.result(1, h, n, now=3.0))  # slowpoke delivers
        assert (final[0][1].hash, final[0][1].nonce) == (h, n)
        assert 10 not in s.jobs  # duplicate withdrawn, job closed

    def test_straggler_withdrawal_survives_chunk_resplitting(self):
        # Dispatch may cut the re-queued duplicate into different chunk
        # shapes; the late Result must still withdraw what remains pending
        # (interval subtraction, not whole-tuple matching).  depth=1 keeps
        # the replacement miner to a single differently-shaped chunk.
        s = Scheduler(min_chunk=300, straggler_min_seconds=1.0, pipeline_depth=1)
        s.miner_joined(1, now=0.0)
        s.client_request(10, DATA, 0, 299, now=0.0)  # miner 1 holds (0,299)
        s.tick(2.0)  # re-queued; no peer yet
        s.min_chunk = 100  # replacement carves a smaller chunk
        acts = s.miner_joined(2, now=2.5)
        req = requests(acts)
        assert (req[0][1].lower, req[0][1].upper) == (0, 99)
        assert list(s.jobs[10].pending) == [(100, 299)]
        # The hung miner delivers its full-range Result after all.
        h, n = honest(DATA, 0, 299)
        assert results(s.result(1, h, n, now=3.0)) == []  # miner 2 still out
        assert list(s.jobs[10].pending) == []  # (100,299) withdrawn, NOT re-swept
        h2, n2 = honest(DATA, 0, 99)
        final = results(s.result(2, h2, n2, now=3.5))
        assert (final[0][1].hash, final[0][1].nonce) == (h, n)

    def test_lost_after_timeout_does_not_duplicate_chunk(self):
        s = Scheduler(min_chunk=100, straggler_min_seconds=1.0)
        s.miner_joined(1, now=0.0)
        s.client_request(10, DATA, 0, 99, now=0.0)
        s.tick(2.0)
        s.lost(1, now=3.0)  # hung miner finally dies
        job = s.jobs[10]
        assert list(job.pending) == [(0, 99)]  # exactly one copy


class TestCheckpointResume:
    def test_resume_skips_completed_subranges(self):
        s = Scheduler(min_chunk=100, max_chunk=100)
        s.miner_joined(1, now=0.0)
        s.client_request(10, DATA, 0, 299, now=0.0)  # chunks of 100
        h0, n0 = honest(DATA, 0, 99)
        s.result(1, h0, n0, now=10.0)  # [0,99] done; [100,199] assigned
        state = s.checkpoint()
        [jobdict] = state["jobs"]
        assert jobdict["best"] == [h0, n0]
        # Remaining = outstanding [100,199] + pending [200,299], merged.
        assert jobdict["remaining"] == [[100, 299]]

        # Fleet restart: fresh scheduler, client resubmits the same job.
        s2 = Scheduler(min_chunk=1000, resume_state=state)
        s2.miner_joined(5, now=0.0)
        acts = s2.client_request(20, DATA, 0, 299, now=0.0)
        req = requests(acts)
        assert (req[0][1].lower, req[0][1].upper) == (100, 299)  # no re-sweep
        h1, n1 = honest(DATA, 100, 299)
        final = results(s2.result(5, h1, n1, now=1.0))
        assert (final[0][1].hash, final[0][1].nonce) == min_hash_range(
            DATA, 0, 299
        )

    def test_resume_fully_swept_job_answers_immediately(self):
        s = Scheduler(min_chunk=1000)
        s.miner_joined(1)
        s.client_request(10, DATA, 0, 99)
        h, n = honest(DATA, 0, 99)
        s.result(1, h, n)
        # Job completed -> nothing to checkpoint for it...
        assert s.checkpoint()["jobs"] == []
        # ...but a checkpoint taken mid-flight with zero remaining resumes
        # to an instant answer.
        state = {
            "version": 1,
            "jobs": [
                {
                    "data": DATA,
                    "lower": 0,
                    "upper": 99,
                    "best": [h, n],
                    "remaining": [],
                }
            ],
        }
        s2 = Scheduler(resume_state=state)
        acts = s2.client_request(20, DATA, 0, 99)
        final = results(acts)
        assert (final[0][1].hash, final[0][1].nonce) == (h, n)

    def test_mismatched_request_does_not_resume(self):
        state = {
            "version": 1,
            "jobs": [
                {
                    "data": DATA,
                    "lower": 0,
                    "upper": 99,
                    "best": [1, 1],
                    "remaining": [],
                }
            ],
        }
        s = Scheduler(min_chunk=1000, resume_state=state)
        s.miner_joined(1)
        # Different range -> a fresh job covering the full range.
        acts = s.client_request(20, DATA, 0, 199)
        req = requests(acts)
        assert (req[0][1].lower, req[0][1].upper) == (0, 199)

    def test_checkpoint_roundtrips_orphaned_progress(self):
        state = {
            "version": 1,
            "jobs": [
                {
                    "data": "x",
                    "lower": 0,
                    "upper": 9,
                    "best": None,
                    "remaining": [[5, 9]],
                }
            ],
        }
        s = Scheduler(resume_state=state)
        assert s.checkpoint()["jobs"] == state["jobs"]

    def test_duplicate_key_entries_merge_not_overwrite(self):
        """A live job and a staler orphaned entry for the same (data, lo, hi)
        used to round-trip last-wins — the orphan could clobber the live
        job's fresher progress.  They must merge: min-fold best, union
        remaining."""
        orphan = {
            "data": DATA,
            "lower": 0,
            "upper": 299,
            "best": [500, 42],
            "remaining": [[0, 299]],  # stale: nothing swept yet
        }
        s = Scheduler(
            min_chunk=100, max_chunk=100,
            resume_state={"version": 1, "jobs": [orphan, dict(orphan)]},
        )
        # Duplicate entries within one load already collapse to one.
        assert len(s.checkpoint()["jobs"]) == 1
        s.miner_joined(1, now=0.0)
        # A DIFFERENT client id resubmits; the resume entry is consumed and
        # the job advances past the orphan's snapshot.
        s.client_request(10, DATA, 0, 299, now=0.0)
        h0, n0 = honest(DATA, 0, 99)
        better = min((h0, n0), (500, 42))
        s.result(1, h0, n0, now=1.0)  # [0,99] swept
        # Re-stage the stale orphan AFTER the live job progressed.
        s.load_checkpoint({"version": 1, "jobs": [orphan]})
        state = s.checkpoint()
        [j] = state["jobs"]
        # best: the min of live progress and the orphan's (real) hash.
        assert j["best"] == list(better)
        # remaining: the union — the stale full-range claim wins space-wise
        # (conservative re-sweep), but fresher best is never lost.
        assert j["remaining"] == [[0, 299]]

        # Round-trip into a fresh scheduler: still one entry, same content.
        s2 = Scheduler(resume_state=state)
        assert s2.checkpoint()["jobs"] == state["jobs"]

    def test_two_resubmits_after_lost_first_resumes_second_restarts(self):
        """The gateway cancels a coalesced job through ``lost()`` when its
        last waiter dies; if TWO clients then resubmit the identical
        signature, exactly one consumes the orphan stash (first come) and
        the other starts full-range — never a double-consume, never a
        lost best-so-far, and the checkpoint folds back to one entry."""
        METRICS.reset()
        s = Scheduler(min_chunk=100, max_chunk=100, validate_results=False)
        s.miner_joined(1, now=0.0)
        s.client_request(10, DATA, 0, 299, now=0.0)
        s.result(1, hash_=700, nonce=5, now=0.5)  # [0,99] swept
        s.lost(10, now=1.0)
        assert METRICS.get("sched.jobs_orphaned") == 1
        s.client_request(20, DATA, 0, 299, now=2.0)
        s.client_request(21, DATA, 0, 299, now=2.0)
        assert METRICS.get("sched.jobs_resumed") == 1  # exactly one resume
        resumed, fresh = s.jobs[20], s.jobs[21]
        assert resumed.best == (700, 5)  # stashed progress carried over
        assert fresh.best is None  # the twin starts from scratch...
        remaining_fresh = list(fresh.pending) + [
            iv for lst in fresh.outstanding.values() for iv in lst
        ]
        assert sorted(remaining_fresh)[0][0] == 0  # ...over the full range
        # One merged checkpoint entry covers both, best preserved.
        [j] = s.checkpoint()["jobs"]
        assert j["best"] == [700, 5]
        assert j["remaining"] == [[0, 299]]

    def test_resume_entry_races_live_identical_twin(self):
        """A staged checkpoint entry consumed by one request while an
        identical twin runs concurrently (the shape behind a gateway
        coalesce racing checkpoint-resume): the resumed job must keep the
        stashed best and skip swept ranges, the twin must sweep the full
        range, and both must answer bit-exact."""
        staged_best = [hash_nonce(DATA, 150), 150]
        state = {
            "version": 1,
            "jobs": [
                {
                    "data": DATA,
                    "lower": 0,
                    "upper": 199,
                    "best": staged_best,
                    "remaining": [[100, 199]],
                }
            ],
        }
        s = Scheduler(min_chunk=1000, resume_state=state)
        s.miner_joined(1, now=0.0)
        s.miner_joined(2, now=0.0)
        s.client_request(10, DATA, 0, 199, now=0.0)  # consumes the stash
        s.client_request(11, DATA, 0, 199, now=0.0)  # identical twin, fresh
        # Miner 1 holds the resumed tail [100,199]; miner 2 the full range.
        assert s.jobs[10].outstanding[1] == [(100, 199)]
        assert s.jobs[11].outstanding[2] == [(0, 199)]
        # Mid-flight, the merged checkpoint is ONE conservative entry.
        [j] = s.checkpoint()["jobs"]
        assert j["best"] == staged_best
        assert j["remaining"] == [[0, 199]]
        h1, n1 = honest(DATA, 100, 199)
        final_a = results(s.result(1, h1, n1, now=1.0))
        assert (final_a[0][1].hash, final_a[0][1].nonce) == min(
            (tuple(staged_best)), (h1, n1)
        )
        h2, n2 = honest(DATA, 0, 199)
        final_b = results(s.result(2, h2, n2, now=1.5))
        assert (final_b[0][1].hash, final_b[0][1].nonce) == (h2, n2)

    def test_two_identical_concurrent_jobs_checkpoint_merges(self):
        """Two clients running the same (data, lower, upper) concurrently
        produce one merged checkpoint entry covering both jobs' unswept
        work and the better best."""
        s = Scheduler(min_chunk=100, max_chunk=100)
        s.miner_joined(1, now=0.0)
        s.miner_joined(2, now=0.0)
        s.client_request(10, DATA, 0, 299, now=0.0)
        s.client_request(11, DATA, 0, 299, now=0.0)
        h0, n0 = honest(DATA, 0, 99)
        s.result(1, h0, n0, now=1.0)  # job 10: [0,99] swept
        [j] = s.checkpoint()["jobs"]
        assert j["best"] == [h0, n0]
        assert j["remaining"] == [[0, 299]]  # job 11 still needs [0,99]


def test_merge_intervals():
    assert _merge_intervals([]) == []
    assert _merge_intervals([(5, 9), (0, 4)]) == [(0, 9)]  # adjacent
    assert _merge_intervals([(0, 9), (3, 5)]) == [(0, 9)]  # contained
    assert _merge_intervals([(0, 2), (4, 6)]) == [(0, 2), (4, 6)]  # gap
    assert _merge_intervals([(0, 5), (3, 8)]) == [(0, 8)]  # overlap


def test_max_chunk_cannot_outgrow_pallas_argmin_guard():
    """Couples the scheduler's chunk cap to the kernel's int32-argmin guard
    (ops/pallas_sha256.py: batch * 10^k lanes must fit int32 or the kernel
    would return silently wrong nonces).  A max_chunk-sized chunk is split
    into dispatches of (batch, 10^k) by the sweep driver, so the binding
    invariant is on the pallas tier's DEFAULTS — build the kernels for a
    full-size chunk's decomposition and let the guard raise if the two
    limits ever drift apart."""
    from bitcoin_miner_tpu.ops.pallas_sha256 import make_pallas_minhash
    from bitcoin_miner_tpu.ops.sweep import (
        _layout_cache,
        auto_tune,
        decompose_range,
    )

    backend, batch, max_k, _sieve, _factored = auto_tune(
        "pallas", None, None
    )
    assert batch * 10**max_k <= 2**31 - 1, "pallas defaults overflow argmin"
    s = Scheduler()
    lo = 10**9
    for group in decompose_range(lo, lo + s.max_chunk - 1, max_k=max_k):
        layout = _layout_cache(b"cmu440", group.d)
        low_pos = layout.digit_pos[layout.digit_count - group.k :]
        # Raises ValueError at construction if batch*10^k overflows int32.
        make_pallas_minhash(layout.n_tail_blocks, low_pos, group.k, batch)
