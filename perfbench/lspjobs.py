"""One job as a user sends it: a fresh LSP connection to the server's public
port, one Request, its Result, close (the CMU client's shape)."""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass
from typing import Optional, Tuple

from bitcoin_miner_tpu import lsp
from bitcoin_miner_tpu.bitcoin.message import Message, MsgType


@dataclass
class Job:
    data: str
    lower: int
    upper: int
    due: float = 0.0  # monotonic time it was due to be sent
    sent: Optional[float] = None
    done: Optional[float] = None
    answer: Optional[Tuple[int, int]] = None

    @property
    def nonces(self) -> int:
        return self.upper - self.lower + 1

    def as_dict(self, t0: float) -> dict:
        rel = lambda t: None if t is None else round(t - t0, 6)  # noqa: E731
        return {
            "data": self.data, "lower": self.lower, "upper": self.upper,
            "due": rel(self.due), "sent": rel(self.sent), "done": rel(self.done),
            "answer": list(self.answer) if self.answer else None,
        }


async def run_job(port: int, job: Job, deadline: float) -> Job:
    """Send ``job`` now; fill in when it was sent, answered, and the answer
    (None when the connection was lost or refused, or no answer came by
    the monotonic time ``deadline``)."""
    job.sent = time.monotonic()
    try:
        client = await asyncio.wait_for(
            lsp.AsyncClient.connect("127.0.0.1", port),
            max(0.0, deadline - job.sent),
        )
    except (lsp.LspError, OSError, asyncio.TimeoutError):
        return job
    try:
        client.write(Message.request(job.data, job.lower, job.upper).marshal())
        while True:
            payload = await asyncio.wait_for(
                client.read(), max(0.0, deadline - time.monotonic())
            )
            msg = Message.unmarshal(payload)
            if msg is not None and msg.type == MsgType.RESULT:
                job.done = time.monotonic()
                job.answer = (msg.hash, msg.nonce)
                break
    except (lsp.LspError, asyncio.TimeoutError):
        pass
    finally:
        try:
            await asyncio.wait_for(client.close(), 5.0)
        except (lsp.LspError, asyncio.TimeoutError):
            pass
    return job
