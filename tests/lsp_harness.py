"""Shared test harness for the LSP suites.

Mirrors the reference's builder-style test systems (``lsp/lsp1_test.go:25-92``
``testSystem`` et al.): a server plus N concurrent clients driven from
threads over real loopback UDP, with the lspnet fault knobs as the fake
network and every timeout denominated in epochs so timing scales with
EpochMillis (lsp/lsp2_test.go:123-127 ``setMaxEpochs`` pattern).
"""

from __future__ import annotations

import asyncio
import random
import threading
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from bitcoin_miner_tpu import lsp, lspnet


def random_port() -> int:
    # The Go suites bind 3000 + rand.Intn(50000) (lsp1_test.go:70-75); we
    # let the OS assign (port 0) where possible, and use this only for
    # slow-start tests that need a port before the server exists.
    return 3000 + random.randint(10000, 50000)


@dataclass
class TestSystem:
    """Builder-style echo test system."""

    __test__ = False  # not a pytest collection target

    num_clients: int = 1
    num_msgs: int = 10
    window: int = 1
    epoch_millis: int = 100
    epoch_limit: int = 5
    max_epochs: int = 60  # global deadline, in epochs
    write_drop: int = 0  # symmetric write-drop percent while echoing
    sleep_max_ms: int = 0  # random client+server delays (setMaxSleepMillis,
    # lsp1_test.go TestBasic7-9 / TestSendReceive3)
    desc: str = ""

    errors: List[str] = field(default_factory=list)
    _threads: List[threading.Thread] = field(default_factory=list)

    @property
    def params(self) -> lsp.Params:
        return lsp.Params(self.epoch_limit, self.epoch_millis, self.window)

    @property
    def deadline(self) -> float:
        return self.max_epochs * self.epoch_millis / 1000.0

    def fail(self, msg: str) -> None:
        self.errors.append(msg)

    def run_echo(self) -> None:
        """N clients each write num_msgs values and verify the echoes
        (lsp1_test.go:124-160 per-client loop)."""
        lspnet.reset_faults()
        server = lsp.Server(0, self.params)
        stop = threading.Event()

        def maybe_sleep() -> None:
            if self.sleep_max_ms:
                import time

                time.sleep(random.uniform(0, self.sleep_max_ms) / 1000.0)

        def server_loop() -> None:
            while not stop.is_set():
                try:
                    cid, payload = server.read()
                    maybe_sleep()
                    server.write(cid, payload)
                except lsp.ConnLostError:
                    continue
                except lsp.LspError:
                    return

        st = threading.Thread(target=server_loop, daemon=True)
        st.start()

        if self.write_drop:
            lspnet.set_write_drop_percent(self.write_drop)

        def client_loop(idx: int) -> None:
            try:
                c = lsp.Client("127.0.0.1", server.port, self.params)
            except lsp.LspError as e:
                self.fail(f"client {idx} connect failed: {e}")
                return
            try:
                for i in range(self.num_msgs):
                    value = f"{idx}:{i}:{random.randint(0, 1_000_000)}".encode()
                    maybe_sleep()
                    c.write(value)
                    got = c.read()
                    if got != value:
                        self.fail(f"client {idx} echo mismatch: {got!r} != {value!r}")
                        return
            except lsp.LspError as e:
                self.fail(f"client {idx} transport error: {e}")
            finally:
                try:
                    c.close()
                except lsp.LspError:
                    pass

        self._threads = [
            threading.Thread(target=client_loop, args=(i,), daemon=True)
            for i in range(self.num_clients)
        ]
        for t in self._threads:
            t.start()
        for t in self._threads:
            t.join(timeout=self.deadline)
            if t.is_alive():
                self.fail(f"deadline exceeded ({self.max_epochs} epochs)")
        stop.set()
        lspnet.reset_faults()
        try:
            server.close()
        except lsp.LspError:
            pass
        assert not self.errors, self.errors


def endpoint_on_port(port: Callable[[], int]):
    """A stand-in for ``lspnet.create_client_endpoint`` that binds the
    loopback port ``port()`` gives (0: any), as a client's OS may hand a
    new client the port another has just closed."""
    from bitcoin_miner_tpu.lspnet.udp import UDPEndpoint, _QueueProtocol

    async def create(host, remote_port, label=None):
        loop = asyncio.get_running_loop()
        transport, protocol = await loop.create_datagram_endpoint(
            _QueueProtocol, local_addr=("127.0.0.1", port())
        )
        return UDPEndpoint(transport, protocol, is_server=False,
                           remote=(host, remote_port), label=label)

    return create


def spawn(fn: Callable[[], None]) -> threading.Thread:
    t = threading.Thread(target=fn, daemon=True)
    t.start()
    return t
