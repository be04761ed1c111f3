"""Unit tests of the pure Scheduler logic (no sockets, no JAX).

The reference has no scheduler tests (its server is a stub); these pin the
behavior SURVEY §3.6 reconstructs from the frozen contracts: join/request/
result folding, adaptive chunking, dead-miner reassignment, dead-client
cancellation, fairness.
"""

from bitcoin_miner_tpu.apps.scheduler import Scheduler
from bitcoin_miner_tpu.bitcoin.hash import min_hash_range
from bitcoin_miner_tpu.bitcoin.message import MsgType


def drain(job_actions):
    return {cid: msg for cid, msg in job_actions}


class TestBasicFlow:
    def test_join_then_request_assigns(self):
        s = Scheduler(validate_results=False, min_chunk=100)
        assert s.miner_joined(1) == []
        actions = s.client_request(10, "data", 0, 99)
        assert len(actions) == 1
        cid, msg = actions[0]
        assert cid == 1
        assert msg.type == MsgType.REQUEST
        assert (msg.lower, msg.upper) == (0, 99)

    def test_request_then_join_assigns(self):
        s = Scheduler(validate_results=False, min_chunk=100)
        assert s.client_request(10, "data", 0, 99) == []
        actions = s.miner_joined(1)
        assert len(actions) == 1
        assert actions[0][0] == 1

    def test_result_completes_job(self):
        s = Scheduler(validate_results=False, min_chunk=1000)
        s.miner_joined(1)
        s.client_request(10, "data", 0, 99)
        actions = s.result(1, hash_=555, nonce=42)
        assert actions[0] == (10, actions[0][1])
        msg = actions[0][1]
        assert msg.type == MsgType.RESULT
        assert (msg.hash, msg.nonce) == (555, 42)
        assert s.jobs == {}
        assert s.miners[1].job is None  # miner idle again

    def test_range_split_across_miners_min_folds(self):
        s = Scheduler(validate_results=False, min_chunk=50)
        for m in (1, 2):
            s.miner_joined(m)
        actions = s.client_request(10, "data", 0, 99)
        assert len(actions) == 2
        ranges = sorted((m.lower, m.upper) for _, m in actions)
        assert ranges == [(0, 49), (50, 99)]
        assert s.result(1, hash_=900, nonce=7) == []  # half done: no reply yet
        final = s.result(2, hash_=300, nonce=61)
        # min-fold picks the smaller hash
        assert final[0][1].hash == 300 and final[0][1].nonce == 61

    def test_tie_break_lowest_nonce(self):
        s = Scheduler(validate_results=False, min_chunk=50)
        s.miner_joined(1)
        s.miner_joined(2)
        s.client_request(10, "d", 0, 99)
        s.result(2, hash_=100, nonce=80)
        final = s.result(1, hash_=100, nonce=3)
        assert final[0][1].nonce == 3

    def test_empty_range_answers_immediately(self):
        s = Scheduler(validate_results=False)
        actions = s.client_request(10, "d", 5, 4)
        assert actions[0][0] == 10
        assert actions[0][1].type == MsgType.RESULT


class TestFaults:
    def test_dead_miner_chunk_reassigned(self):
        s = Scheduler(validate_results=False, min_chunk=1000)
        s.miner_joined(1)
        s.client_request(10, "d", 0, 499)
        actions = s.lost(1)  # miner dies mid-chunk
        assert actions == []  # nobody to reassign to yet
        actions = s.miner_joined(2)  # replacement arrives
        assert len(actions) == 1
        assert (actions[0][1].lower, actions[0][1].upper) == (0, 499)

    def test_dead_miner_with_idle_peer_reassigns_immediately(self):
        s = Scheduler(validate_results=False, min_chunk=1000)
        s.miner_joined(1)
        s.miner_joined(2)
        s.client_request(10, "d", 0, 499)  # one chunk -> one miner busy
        busy = next(m for m in s.miners.values() if m.job is not None).conn_id
        actions = s.lost(busy)
        assert len(actions) == 1  # idle peer picks it straight up
        assert (actions[0][1].lower, actions[0][1].upper) == (0, 499)

    def test_dead_client_drops_job_and_result_ignored(self):
        s = Scheduler(validate_results=False, min_chunk=1000)
        s.miner_joined(1)
        s.client_request(10, "d", 0, 499)
        assert s.lost(10) == []  # client dies: job cancelled silently
        assert s.jobs == {}
        actions = s.result(1, hash_=5, nonce=5)  # stale result arrives
        assert actions == []  # ignored, miner back to idle
        assert s.miners[1].job is None

    def test_miner_death_preserves_low_nonce_order(self):
        s = Scheduler(validate_results=False, min_chunk=100, max_chunk=100)
        s.miner_joined(1)
        s.client_request(10, "d", 0, 299)  # miner 1 gets [0,99]
        s.lost(1)
        actions = s.miner_joined(2)  # must get [0,99] back first, not [100,199]
        assert (actions[0][1].lower, actions[0][1].upper) == (0, 99)


class TestPipelining:
    def test_results_match_fifo(self):
        # Two chunks queued at one miner; results close them oldest-first.
        s = Scheduler(validate_results=False, min_chunk=100, max_chunk=100)
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 299, now=0.0)
        assert [a.interval for a in s.miners[1].queue] == [(0, 99), (100, 199)]
        s.result(1, hash_=5, nonce=7, now=1.0)
        # (0,99) closed; (100,199) promoted to front; refill appended.
        assert s.miners[1].queue[0].interval == (100, 199)
        assert 0 not in [iv for lst in s.jobs[10].outstanding.values() for iv in lst]

    def test_rate_uses_result_gap_not_assignment_time(self):
        # Both chunks assigned at t=0; results at t=10 and t=11.  The second
        # sample must be size/1s (result gap), not size/11s.
        s = Scheduler(
            validate_results=False, min_chunk=100, max_chunk=100, rate_alpha=1.0
        )
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 299, now=0.0)
        s.result(1, hash_=5, nonce=7, now=10.0)
        assert s.miners[1].rate == 100 / 10.0
        s.result(1, hash_=5, nonce=107, now=11.0)
        assert s.miners[1].rate == 100 / 1.0

    def test_lost_miner_requeues_all_chunks_in_order(self):
        s = Scheduler(validate_results=False, min_chunk=100, max_chunk=100)
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 299, now=0.0)  # holds (0,99),(100,199)
        s.lost(1, now=1.0)
        assert list(s.jobs[10].pending) == [(0, 99), (100, 199), (200, 299)]

    def test_evicted_liar_requeues_queued_chunks(self):
        from bitcoin_miner_tpu.bitcoin.hash import min_hash_range

        s = Scheduler(min_chunk=100, max_chunk=100, max_rejects=1)
        s.miner_joined(1, now=0.0)
        s.client_request(10, "cmu440", 0, 299, now=0.0)
        s.result(1, hash_=1, nonce=2, now=1.0)  # lie -> instant eviction
        assert 1 not in s.miners
        # Both the lied-about front chunk AND the queued second chunk are
        # back in pending, in nonce order.
        assert list(s.jobs[10].pending) == [(0, 99), (100, 199), (200, 299)]
        s.miner_joined(2, now=2.0)
        h, n = min_hash_range("cmu440", 0, 299)
        for lo in (0, 100, 200):
            hh, nn = min_hash_range("cmu440", lo, lo + 99)
            final = s.result(2, hh, nn, now=3.0 + lo)
        assert final[0][1].hash == h and final[0][1].nonce == n

    def test_straggler_cascade_times_out_successor(self):
        # Front times out at t=11; the queued successor's clock starts
        # there, so it times out ~10s later, not immediately.
        s = Scheduler(
            validate_results=False,
            min_chunk=100,
            max_chunk=100,
            straggler_min_seconds=10.0,
        )
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 299, now=0.0)
        s.tick(11.0)
        assert [a.timed_out for a in s.miners[1].queue] == [True, False]
        assert s.tick(12.0) == []  # successor's deadline not reached
        s.tick(22.0)
        assert [a.timed_out for a in s.miners[1].queue] == [True, True]
        # Both duplicates pending (plus the never-assigned third chunk).
        assert sorted(s.jobs[10].pending) == [(0, 99), (100, 199), (200, 299)]

    def test_hung_miner_gets_no_new_work(self):
        s = Scheduler(
            validate_results=False,
            min_chunk=100,
            max_chunk=100,
            straggler_min_seconds=10.0,
        )
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 99, now=0.0)
        assert s.tick(11.0) == []  # re-queued, but the only miner is hung
        assert list(s.jobs[10].pending) == [(0, 99)]
        assert len(s.miners[1].queue) == 1  # NOT handed its own duplicate

    def test_ramp_boost_grows_chunks_geometrically(self):
        # A fast miner completing min_chunk in a blink gets ramp_factor x
        # its last chunk, not just rate*target (which the per-chunk latency
        # in the EWMA understates during ramp) — snapped to the nearest
        # 10^k rung of the aligned size ladder (8000 -> 10^4, ISSUE 10).
        s = Scheduler(
            validate_results=False,
            min_chunk=1000,
            target_chunk_seconds=0.5,
            rate_alpha=1.0,
            pipeline_depth=1,
            ramp_factor=8,
        )
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 10**9, now=0.0)
        # 1000 nonces in 0.2s -> EWMA rate 5000/s -> rate-based next chunk
        # would be 2500; the boost gives 8x1000 = 8000 -> rung 10^4.  The
        # carve cuts on the rung boundary, so lower=1000 runs to 9999 (a
        # runt up to the boundary); the NEXT chunk is a full aligned rung.
        actions = s.result(1, hash_=5, nonce=7, now=0.2)
        nxt = actions[0][1]
        assert (nxt.lower, nxt.upper) == (1000, 9999)
        # Still fast -> the ramp keeps climbing the ladder: next chunk is
        # a full aligned rung (10^5 here: 8x the 9000-nonce runt, snapped).
        actions = s.result(1, hash_=5, nonce=nxt.lower, now=0.4)
        nxt = actions[0][1]
        assert (nxt.lower, nxt.upper) == (10_000, 99_999)
        # Legacy (ladder off) keeps the raw boosted size.
        s2 = Scheduler(
            validate_results=False, min_chunk=1000,
            target_chunk_seconds=0.5, rate_alpha=1.0,
            pipeline_depth=1, ramp_factor=8, adaptive_chunks=False,
        )
        s2.miner_joined(1, now=0.0)
        s2.client_request(10, "d", 0, 10**9, now=0.0)
        actions = s2.result(1, hash_=5, nonce=7, now=0.2)
        assert actions[0][1].upper - actions[0][1].lower + 1 == 8000


class TestAdaptiveChunking:
    def test_fast_miner_gets_bigger_chunks(self):
        s = Scheduler(validate_results=False, min_chunk=100, max_chunk=10**9, target_chunk_seconds=1.0)
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 10**9, now=0.0)
        # first chunk is min_chunk (rate unknown)
        first = s.miners[1].interval
        assert first == (0, 99)
        # completes 100 nonces in 1 ms -> rate 1e5/s -> next chunk ~1e5
        actions = s.result(1, hash_=7, nonce=0, now=0.001)
        nxt = actions[0][1]
        size = nxt.upper - nxt.lower + 1
        assert 50_000 <= size <= 200_000

    def test_chunk_capped_at_max(self):
        s = Scheduler(validate_results=False, min_chunk=10, max_chunk=1000, target_chunk_seconds=1.0)
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 10**9, now=0.0)
        actions = s.result(1, hash_=7, nonce=0, now=1e-9)  # absurd rate
        nxt = actions[0][1]
        # Capped at max_chunk (the 10^3 rung) and cut on the rung
        # boundary: lower=20 (after the two cold chunks) runs to 999.
        assert nxt.upper - nxt.lower + 1 <= 1000
        assert (nxt.upper + 1) % 1000 == 0


class TestStealScan:
    """Straggler tail re-dispatch (ISSUE 10): a slow chunk's tail is
    handed to an idle miner, first completed sub-interval wins, and the
    interval-subtraction bookkeeping keeps every completion order
    bit-exact against a from-scratch sweep."""

    def _one_chunk_fleet(self, **kw):
        # Whole range in ONE chunk at miner 1; miner 2 idle.
        kw.setdefault("validate_results", False)
        kw.setdefault("min_chunk", 10**6)
        s = Scheduler(**kw)
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 999, now=0.0)
        s.miner_joined(2, now=0.0)
        return s

    def test_marked_straggler_tail_stolen_to_idle_miner(self):
        s = self._one_chunk_fleet()
        s.mark_straggler(1)  # the PR-7 fleet detector's external naming
        acts = s.tick(now=0.1)  # no age evidence needed: mark suffices
        assert len(acts) == 1
        cid, msg = acts[0]
        assert cid == 2 and msg.type == MsgType.REQUEST
        # The upper half: the straggler sweeps low nonces first.
        assert (msg.lower, msg.upper) == (500, 999)
        # The holder still owes the WHOLE interval; the tail is recorded
        # as its duplicated portion.
        assert s.miners[1].queue[0].stolen == (500, 999)

    def test_age_based_steal_needs_fleet_p50_evidence(self):
        s = Scheduler(
            validate_results=False, min_chunk=100, max_chunk=100,
            pipeline_depth=1, steal_min_seconds=0.0, steal_min_samples=4,
            straggler_min_seconds=0.0,
        )
        s.miner_joined(1, now=0.0)
        # Exactly 5 chunks: after 4 completions the LAST chunk is the
        # front and the job has no pending work left for a joiner.
        s.client_request(10, "d", 0, 499, now=0.0)
        # Build fleet evidence: 4 accepted chunks at ~0.1 s each
        # (miner EWMA rate ~1000 nonces/s).
        for i in range(4):
            s.result(1, hash_=5, nonce=100 * i, now=0.1 * (i + 1))
        s.miner_joined(2, now=0.45)  # idle thief, nothing to dispatch
        # Miner 1's running chunk started at 0.4; at 0.5 it is younger
        # than steal_factor(2.0) x p50(0.1) -> no steal yet.
        assert s.tick(now=0.5) == []
        # Age evidence is in at 0.7, but the rate-aware cut point (ISSUE
        # 13 satellite) says the straggler's ~1000 n/s EWMA finishes the
        # remaining 100 nonces well before its re-queue deadline
        # (0.4 + 4.0 x 0.1 = 0.8) -> stealing would be pure duplication.
        assert s.tick(now=0.7) == []
        # At 0.75 only ~50 nonces fit before the deadline: the
        # unfinishable tail (and ONLY it) is re-dispatched to the thief.
        acts = s.tick(now=0.75)
        assert [m.type for _, m in acts] == [MsgType.REQUEST]
        assert acts[0][0] == 2
        msg = acts[0][1]
        assert (msg.lower, msg.upper) == (450, 499)
        assert s.miners[1].queue[0].stolen == (450, 499)

    def test_rate_aware_cut_grows_as_deadline_nears(self):
        """The satellite's core property: the stolen tail is exactly the
        portion the straggler's EWMA rate cannot cover by its re-queue
        deadline, so successive ticks (deadline approaching, nothing
        answered) would steal strictly more."""
        def fleet():
            s = Scheduler(
                validate_results=False, min_chunk=1000, max_chunk=1000,
                pipeline_depth=1, steal_min_seconds=0.0,
                steal_min_samples=1, straggler_min_seconds=0.0,
            )
            s.miner_joined(1, now=0.0)
            s.client_request(10, "d", 0, 1999, now=0.0)
            # One completed chunk: rate = 1000/1.0 = 1000 n/s, p50 = 1 s.
            s.result(1, hash_=5, nonce=7, now=1.0)
            s.miner_joined(2, now=1.0)
            return s

        # Chunk [1000, 1999] started at 1.0; re-queue deadline = 1.0 +
        # 4.0 x (1000/1000) = 5.0.  At now=4.25 the straggler covers
        # 1000 x 0.75 = 750 more nonces -> steal [1750, 1999].  (Times
        # are binary-exact so int() truncation is deterministic.)
        s = fleet()
        acts = s.tick(now=4.25)
        (thief, msg), = acts
        assert thief == 2 and (msg.lower, msg.upper) == (1750, 1999)
        # Closer to the deadline the unfinishable tail is larger: at
        # now=4.75 only 250 nonces fit -> steal [1250, 1999].
        s = fleet()
        acts = s.tick(now=4.75)
        (thief, msg), = acts
        assert thief == 2 and (msg.lower, msg.upper) == (1250, 1999)

    def test_marked_straggler_ignores_own_rate(self):
        """An externally marked miner (fleet-detector leave-one-out
        evidence) keeps the legacy half split even when its own EWMA
        claims it finishes in time — the mark exists because that EWMA
        is not trustworthy."""
        s = Scheduler(
            validate_results=False, min_chunk=1000, max_chunk=1000,
            pipeline_depth=1, steal_min_seconds=0.0, steal_min_samples=64,
        )
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 1999, now=0.0)
        s.result(1, hash_=5, nonce=7, now=0.1)  # EWMA 10^4 n/s: "fast"
        s.miner_joined(2, now=0.1)
        s.mark_straggler(1)
        acts = s.tick(now=0.2)
        (thief, msg), = acts
        assert thief == 2 and (msg.lower, msg.upper) == (1500, 1999)

    def test_cold_fleet_never_steals_on_guesses(self):
        s = self._one_chunk_fleet(steal_min_seconds=0.0)
        # No chunk has EVER completed: no p50, no steal however old (5 s
        # stays under the full straggler re-queue's 10 s floor).
        assert s.tick(now=5.0) == []

    def test_steal_flagged_miner_gets_no_new_work(self):
        s = self._one_chunk_fleet()
        s.mark_straggler(1)
        s.tick(now=0.1)
        # A second job: every chunk must route around the flagged holder.
        acts = s.client_request(11, "e", 0, 999, now=0.2)
        assert {cid for cid, _ in acts} == {2}

    def test_stolen_front_never_restolen(self):
        s = self._one_chunk_fleet()
        s.mark_straggler(1)
        s.tick(now=0.1)
        s.miner_joined(3, now=0.2)  # another idle miner appears
        s.mark_straggler(1)
        assert s.tick(now=0.3) == []  # escalation is the full re-queue

    def test_valid_answer_clears_stale_straggler_mark(self):
        """A mark that found no idle thief must die when the miner
        answers: stale fleet-detector evidence cannot steal from a
        fresh, healthy chunk minutes later."""
        s = Scheduler(validate_results=False, min_chunk=10**6)
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 999, now=0.0)
        s.mark_straggler(1)  # no idle miner exists: mark cannot act
        assert s.tick(now=0.1) == []
        s.result(1, hash_=5, nonce=7, now=0.2)  # the miner ANSWERS
        s.client_request(11, "e", 0, 999, now=0.3)  # fresh chunk, miner 1
        s.miner_joined(2, now=0.4)  # an idle thief appears later
        # The fresh front chunk is not stolen on the stale mark (and is
        # far too young for age evidence).
        assert s.tick(now=0.5) == []
        s = Scheduler(validate_results=False, min_chunk=10**6)
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 999, now=0.0, prefill=True)
        s.miner_joined(2, now=0.0)
        s.mark_straggler(1)
        assert s.tick(now=0.1) == []  # speculation isn't worth duplicating

    def test_split_on_steal_bit_exact_property(self):
        """The ISSUE 10 property: random split points over real hashlib
        minima — whichever sub-interval completes first, the winner's
        fold plus the discarded loser's overlap equals a from-scratch
        sweep, with oracle validation ON."""
        import random

        rng = random.Random(0xBEEF)
        for trial in range(6):
            lo = rng.randrange(0, 800)
            hi = lo + rng.randrange(40, 400)
            data = f"steal-{trial}"
            order = trial % 3
            s = Scheduler(min_chunk=10**6, pipeline_depth=1)
            s.miner_joined(1, now=0.0)
            s.client_request(10, data, lo, hi, now=0.0)
            s.miner_joined(2, now=0.0)
            s.mark_straggler(1)
            acts = s.tick(now=0.5)
            (thief, tail_msg), = acts
            t_lo, t_hi = tail_msg.lower, tail_msg.upper
            assert thief == 2 and lo < t_lo <= t_hi == hi
            done = []
            if order == 0:
                # Thief first, then the straggler's full interval: the
                # losing duplicate folds harmlessly (min over a superset).
                done += s.result(2, *min_hash_range(data, t_lo, t_hi), now=1.0)
                done += s.result(1, *min_hash_range(data, lo, hi), now=2.0)
            elif order == 1:
                # Straggler's full interval first: it wins outright, the
                # thief's in-flight duplicate is withdrawn/ignored.
                done += s.result(1, *min_hash_range(data, lo, hi), now=1.0)
                done += s.result(2, *min_hash_range(data, t_lo, t_hi), now=2.0)
            else:
                # Straggler never answers: the full straggler re-queue
                # escalates (head only — the tail copy is already live),
                # and the thief sweeps both halves.
                s.tick(now=100.0)  # past straggler_min_seconds
                acts = s.result(2, *min_hash_range(data, t_lo, t_hi), now=101.0)
                heads = [
                    (m.lower, m.upper) for cid, m in acts
                    if cid == 2 and m.type == MsgType.REQUEST
                ]
                assert heads == [(lo, t_lo - 1)]
                done += acts
                done += s.result(2, *min_hash_range(data, lo, t_lo - 1), now=102.0)
            final = [(cid, m) for cid, m in done if m.type == MsgType.RESULT]
            assert len(final) == 1 and final[0][0] == 10
            want = min_hash_range(data, lo, hi)
            assert (final[0][1].hash, final[0][1].nonce) == want

    def test_late_straggler_result_withdraws_tail_duplicate(self):
        """Thief still computing when the straggler answers after all:
        the tail's PENDING portion is withdrawn so it never re-dispatches,
        and the job completes on the straggler's fold alone."""
        s = Scheduler(validate_results=False, min_chunk=10**6)
        s.miner_joined(1, now=0.0)
        s.client_request(10, "d", 0, 999, now=0.0)
        s.mark_straggler(1)
        assert s.tick(now=0.1) == []  # no idle miner: tail stays pending?
        # No steal happened (no idle miner); now one appears and the
        # steal lands, but the thief dies before answering.
        s.miner_joined(2, now=0.2)
        s.mark_straggler(1)
        s.tick(now=0.3)
        s.lost(2, now=0.4)  # thief dies: tail back to pending
        done = s.result(1, hash_=5, nonce=3, now=0.5)
        final = [(cid, m) for cid, m in done if m.type == MsgType.RESULT]
        assert len(final) == 1 and final[0][0] == 10
        assert s.jobs == {}  # nothing pending: duplicate fully withdrawn


class TestFairness:
    def test_round_robin_across_jobs(self):
        s = Scheduler(validate_results=False, min_chunk=10, max_chunk=10)
        s.client_request(10, "a", 0, 99)
        s.client_request(11, "b", 0, 99)
        served = []
        for m in range(1, 5):
            for cid, msg in s.miner_joined(m):
                served.append(msg.data)
        # Each join fills the miner's pipeline (depth 2), round-robin
        # across jobs: both jobs get an equal share.
        assert served.count("a") == 4 and served.count("b") == 4

    def test_pipeline_fills_breadth_first(self):
        # With 2 miners and depth 2, every miner must hold its FIRST chunk
        # before anyone is handed a second.
        s = Scheduler(validate_results=False, min_chunk=10, max_chunk=10)
        s.miner_joined(1)
        s.miner_joined(2)
        actions = s.client_request(10, "a", 0, 39)
        order = [cid for cid, _ in actions]
        assert sorted(order[:2]) == [1, 2]  # level 0 first
        assert sorted(order[2:]) == [1, 2]  # then level 1
        assert all(len(m.queue) == 2 for m in s.miners.values())

    def test_duplicate_join_ignored(self):
        s = Scheduler(validate_results=False)
        s.miner_joined(1)
        assert s.miner_joined(1) == []
        assert len(s.miners) == 1

    def test_second_request_on_same_conn_ignored(self):
        s = Scheduler(validate_results=False, min_chunk=10**6)
        s.miner_joined(1)
        s.client_request(10, "a", 0, 9)
        assert s.client_request(10, "b", 0, 9) == []

    def test_stats(self):
        s = Scheduler(validate_results=False, min_chunk=10, max_chunk=10)
        s.miner_joined(1)
        s.client_request(10, "a", 0, 99)
        st = s.stats()
        assert st["miners"] == 1 and st["idle_miners"] == 0
        # depth-2 pipeline: the lone miner holds two chunks.
        assert st["jobs"] == 1 and st["outstanding_chunks"] == 2


class TestAdaptiveDepth:
    """Adaptive pipeline depth (ISSUE 14 satellite, PR-10 carry-over):
    the per-miner assignment window tracks the observed per-dispatch
    device latency instead of the static 2 — deep enough to hide a
    high dispatch+fetch latency, shallow when latency doesn't
    warrant it (which also keeps enqueue-time sieve thresholds fresh).
    The latency provider is injected so these stay deterministic."""

    def _sched(self, latency, **kw):
        return Scheduler(
            validate_results=False,
            min_chunk=10,
            max_chunk=10,
            target_chunk_seconds=0.5,
            adaptive_depth=True,
            dispatch_latency=lambda: latency,
            **kw,
        )

    def test_static_without_flag(self):
        s = Scheduler(validate_results=False)
        assert s.effective_depth() == s.pipeline_depth == 2
        s.tick(0.0)
        assert s.effective_depth() == 2

    def test_no_evidence_keeps_configured_depth(self):
        s = self._sched(None)
        s.tick(0.0)
        assert s.effective_depth() == 2

    def test_high_latency_deepens_window(self):
        # p50 2s against a 0.5s chunk target wants 1 + ceil(4) = 5,
        # clamped to depth_cap.
        s = self._sched(2.0, depth_cap=4)
        s.tick(0.0)
        assert s.effective_depth() == 4

    def test_low_latency_shallows_window_to_one(self):
        # Sub-millisecond dispatches (in-process fleets): nothing to
        # hide, so one chunk in flight — the freshest sieve thresholds.
        s = self._sched(0.0)
        s.tick(0.0)
        assert s.effective_depth() == 1

    def test_moderate_latency_keeps_two(self):
        s = self._sched(0.2)  # ceil(0.4) = 1 -> depth 2, the old static
        s.tick(0.0)
        assert s.effective_depth() == 2

    def test_depth_governs_assignment_window(self):
        # With latency evidence saying depth 1, a lone miner holds ONE
        # chunk; flip the evidence to 2s and the next tick re-deepens.
        lat = {"v": 0.0}
        s = Scheduler(
            validate_results=False,
            min_chunk=10,
            max_chunk=10,
            target_chunk_seconds=0.5,
            adaptive_depth=True,
            dispatch_latency=lambda: lat["v"],
        )
        s.tick(0.0)
        s.miner_joined(1)
        s.client_request(10, "a", 0, 99)
        assert s.stats()["outstanding_chunks"] == 1
        lat["v"] = 2.0
        actions = s.tick(1.0)
        # The deeper window back-fills the queue on the same tick.
        assert s.stats()["outstanding_chunks"] >= 2
        assert all(m.type == MsgType.REQUEST for _, m in actions)

    def test_depth_adapt_counts_metric(self):
        from bitcoin_miner_tpu.utils.metrics import METRICS

        before = METRICS.get("sched.depth_adapt")
        s = self._sched(2.0)
        s.tick(0.0)
        assert METRICS.get("sched.depth_adapt") == before + 1
        s.tick(1.0)  # unchanged evidence: no second bump
        assert METRICS.get("sched.depth_adapt") == before + 1
