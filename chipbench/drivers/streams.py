"""The query streams of TPC-H's throughput test (v3.0.1, clause 5.3.4):
``TENANTS`` tenants, one stream each, reach the shuffle service together.

In pass ``r`` tenant ``t`` submits a shuffle of the pool's table
``(r + t) % pool`` to the admission queue, then one ``run_pending()`` runs
the pass: the batch probe groups the four, one vmapped replay serves them,
and each member replays from its slice.  The next pass starts when the last
one's outputs are on the host (a closed loop of passes).  A shuffle that did
not run as a member of one batch of ``TENANTS`` is recorded as a failure,
since the cell measures that path."""
from chipbench.window import closed_loop

TENANTS = 4


class Driver:
    def __init__(self, session):
        """Set-up: the tenants, then warm passes until every tenant has met
        every table of the pool (pass 0 instantiates each tenant's plan) and
        the vmapped program has compiled (pass 1)."""
        self.s = session
        self.clients = [session.cluster.tenant(f"s{t}") for t in range(TENANTS)]
        self.passes = 0
        for r in range(max(len(session.pool), 2)):
            with session.annotate(f"chipbench.setup.pass{r}"):
                self.run_pass()

    def run_pass(self) -> list:
        """One pass: each tenant's submission, then ``run_pending()``;
        (table, result or exception) per tenant."""
        s = self.s
        ks = [self.passes + t for t in range(TENANTS)]
        self.passes += 1
        try:
            tickets = [c.submit(*s.args(k), **s.kwargs)
                       for c, k in zip(self.clients, ks)]
            out = s.cluster.run_pending()
        except Exception as exc:                # counted as failed; run goes on
            return [(k, exc) for k in ks]
        full = {tk for b in s.cluster.last_schedule()["batches"]
                if b["size"] == TENANTS for tk in b["tickets"]}
        done = []
        for k, tk in zip(ks, tickets):
            res = out[tk]
            if not isinstance(res, Exception) and not (res.batched and tk in full):
                res = RuntimeError(
                    f"shuffle of table {k % len(s.pool)} not batched "
                    f"{TENANTS} to a dispatch (batched: {res.batched}, "
                    f"fallback_reason: {res.fallback_reason})")
            done.append((k, res))
        return done

    def window(self, seconds, max_calls=None):
        """Passes back to back; each pass's interval once per member, so
        that the window counts shuffles."""
        s = self.s

        def one_pass(_i):
            with s.annotate(s.CALL):
                done = self.run_pass()
            for k, res in done:
                s.record(k, res)
        calls, elapsed = closed_loop(one_pass, seconds, max_calls=max_calls)
        return [c for c in calls for _ in self.clients], elapsed
