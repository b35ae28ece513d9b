"""One client in a closed loop, as one query stream is: it sends the next
shuffle as soon as the last one's outputs are on the host, taking the pool's
tables in turn."""
from chipbench.window import closed_loop

WARM = ("instantiate", "replay")


class Driver:
    def __init__(self, session):
        """Set-up: the client, and the cell's one shape warmed by an
        instantiating call and a first replay."""
        self.s = session
        self.client = session.cluster.tenant("client")
        for k, step in enumerate(WARM):
            with session.annotate(f"chipbench.setup.{step}"):
                session.shuffle(self.client, k)

    def window(self, seconds, max_calls=None):
        s = self.s

        def call(i):
            k = len(WARM) + i
            with s.annotate(s.CALL):
                try:
                    res = s.shuffle(self.client, k)
                except Exception as exc:        # counted as failed; run goes on
                    res = exc
            s.record(k, res)
        return closed_loop(call, seconds, max_calls=max_calls)
