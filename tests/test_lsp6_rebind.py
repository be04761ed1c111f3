"""Suite 6: connection ids keep two connections from one address apart.

LSP has no close message, so a finished client's connection lingers on the
server for EpochLimit epochs, and a new client may be bound to the same
UDP port meanwhile (the CMU client's shape: one connection per job, many
users).  The server must tell that new client from the retry of a lost
connect-ack, and a client must read nothing that names another
connection's id.
"""

import asyncio
import queue
import socket
import threading
import time

import pytest

from bitcoin_miner_tpu import lsp, lspnet
from bitcoin_miner_tpu.lsp.message import Message, MsgType
from bitcoin_miner_tpu.utils.metrics import METRICS
from lsp_harness import endpoint_on_port, spawn

EPOCH_MS = 100
EPOCH_S = EPOCH_MS / 1000


def params(limit=5):
    return lsp.Params(epoch_limit=limit, epoch_millis=EPOCH_MS, window_size=1)


@pytest.fixture(autouse=True)
def _reset_faults():
    lspnet.reset_faults()
    yield
    lspnet.reset_faults()


def server_reads(server):
    """Everything ``server.read()`` yields, on a queue: (conn_id, payload)
    pairs and the errors it raises."""
    out = queue.Queue()

    def loop():
        while True:
            try:
                out.put(server.read())
            except lsp.ConnClosedError:
                return
            except lsp.LspError as e:
                out.put(e)

    spawn(loop)
    return out


def bind_clients_to(monkeypatch, port):
    """Every client made from here on binds ``port`` on loopback."""
    monkeypatch.setattr(
        lspnet, "create_client_endpoint", endpoint_on_port(lambda: port))


def test_returning_port_gets_a_new_connection(monkeypatch):
    rebound0 = METRICS.get("lsp.connects_rebound")
    server = lsp.Server(0, params())
    reads = server_reads(server)
    try:
        first = lsp.Client("127.0.0.1", server.port, params())
        port = first._c._endpoint.local_addr[1]
        first.write(b"job 1")
        old_id, payload = reads.get(timeout=5)
        assert payload == b"job 1"
        server.write(old_id, b"result 1")
        assert first.read(timeout=5) == b"result 1"
        first.close()

        # A new user handed the same port while the old conn lingers.
        bind_clients_to(monkeypatch, port)
        second = lsp.Client("127.0.0.1", server.port, params())
        assert second._c._endpoint.local_addr[1] == port
        assert second.conn_id() != old_id
        second.write(b"job 2")
        got = [reads.get(timeout=5), reads.get(timeout=5)]
        lost = [g for g in got if isinstance(g, lsp.ConnLostError)]
        data = [g for g in got if not isinstance(g, Exception)]
        assert [e.conn_id for e in lost] == [old_id]
        assert data == [(second.conn_id(), b"job 2")]
        server.write(second.conn_id(), b"result 2")
        assert second.read(timeout=5) == b"result 2"
        assert METRICS.get("lsp.connects_rebound") - rebound0 == 1
        second.close()
    finally:
        server.close()


def test_retried_connect_keeps_its_connection():
    """The server's connect-acks are lost for two epochs: the client's
    retried Connects are re-acked with the one connection."""
    deduped0 = METRICS.get("lsp.connects_deduped")
    rebound0 = METRICS.get("lsp.connects_rebound")
    server = lsp.Server(0, params())
    reads = server_reads(server)
    try:
        lspnet.set_server_write_drop_percent(100)
        made = queue.Queue()
        spawn(lambda: made.put(lsp.Client("127.0.0.1", server.port, params())))
        time.sleep(2.5 * EPOCH_S)
        lspnet.set_server_write_drop_percent(0)
        client = made.get(timeout=5)
        client.write(b"once")
        cid, payload = reads.get(timeout=5)
        assert (cid, payload) == (client.conn_id(), b"once")
        assert server.conns_live() == 1
        assert METRICS.get("lsp.connects_deduped") - deduped0 >= 2
        assert METRICS.get("lsp.connects_rebound") == rebound0
        client.close()
    finally:
        server.close()


class FakeServer:
    """A bare UDP socket in the server's place, so a test can send a client
    whatever datagrams it likes from the server's address."""

    def __init__(self) -> None:
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.settimeout(5)
        self.port = self.sock.getsockname()[1]
        self.client = None

    def recv(self) -> Message:
        data, self.client = self.sock.recvfrom(2048)
        return Message.unmarshal(data)

    def send(self, msg: Message) -> None:
        self.sock.sendto(msg.marshal(), self.client)

    def close(self) -> None:
        self.sock.close()


def handshake(fake, stale_first):
    """Connect a client to ``fake``; before the true connect-ack (id 8) it
    hears ``stale_first``, messages of an old connection (id 7)."""
    made = queue.Queue()
    spawn(lambda: made.put(lsp.Client("127.0.0.1", fake.port, params())))
    assert fake.recv().type == MsgType.CONNECT
    for msg in stale_first:
        fake.send(msg)
    time.sleep(EPOCH_S / 2)
    fake.send(Message.ack(8, 0))
    return made.get(timeout=5)


def test_stale_ids_never_reach_a_new_client():
    fake = FakeServer()
    try:
        client = handshake(fake, [
            Message.data(7, 1, 6, b"stale1"), Message.ack(7, 0),
        ])
        assert client.conn_id() == 8
        fake.send(Message.data(7, 1, 6, b"forged"))
        fake.send(Message.ack(7, 0))
        fake.send(Message.data(8, 1, 4, b"real"))
        assert client.read(timeout=5) == b"real"
        with pytest.raises(TimeoutError):
            client.read(timeout=3 * EPOCH_S)
        # The client acked only its own connection's data.
        acked = set()
        fake.sock.settimeout(EPOCH_S)
        try:
            while True:
                msg = fake.recv()
                if msg.type == MsgType.ACK and msg.seq_num > 0:
                    acked.add((msg.conn_id, msg.seq_num))
        except socket.timeout:
            pass
        assert acked == {(8, 1)}
        client.close()
    finally:
        fake.close()


def test_stale_ids_do_not_keep_a_connection_alive():
    """Only messages of its own id count as the server's signs of life."""
    fake = FakeServer()
    try:
        client = handshake(fake, [])
        assert client.conn_id() == 8
        stop = threading.Event()

        def stale_beats():
            while not stop.is_set():
                fake.send(Message.ack(7, 0))
                fake.send(Message.data(7, 1, 1, b"x"))
                stop.wait(EPOCH_S / 3)

        spawn(stale_beats)
        t0 = time.monotonic()
        try:
            with pytest.raises(lsp.ConnLostError):
                client.read(timeout=20 * EPOCH_S)
        finally:
            stop.set()
        assert time.monotonic() - t0 < 15 * EPOCH_S
        client.close()
    finally:
        fake.close()


def holding_second_connect():
    """A client endpoint factory whose second Connect is overtaken by the
    first Data: the network holds the retry and delivers it just after."""
    from bitcoin_miner_tpu.lspnet.udp import UDPEndpoint, _QueueProtocol

    class Endpoint(UDPEndpoint):
        connects = 0
        held = None

        def send(self, data, addr=None):
            msg = Message.unmarshal(data)
            if msg is not None and msg.type == MsgType.CONNECT:
                self.connects += 1
                if self.connects == 2:
                    self.held = (data, addr)
                    return
            super().send(data, addr)
            if msg is not None and msg.type == MsgType.DATA and self.held:
                super().send(*self.held)
                self.held = None

    async def create(host, remote_port, label=None):
        loop = asyncio.get_running_loop()
        transport, protocol = await loop.create_datagram_endpoint(
            _QueueProtocol, local_addr=("127.0.0.1", 0)
        )
        return Endpoint(transport, protocol, is_server=False,
                        remote=(host, remote_port), label=label)

    return create


def test_retry_overtaken_by_first_data_loses_the_connection(monkeypatch):
    """The trade-off of telling a returning port from a retry by what the
    connection has heard (module docstring of ``lsp.aio``): a connect-ack
    late by more than an epoch, and the client's retried Connect delivered
    after its first Data, reads as a new client on that port.  The live
    connection is then lost on both ends, as a partition would lose it:
    the application reads its Request and then ``ConnLostError``, the
    client reads a loss within EpochLimit epochs, and nothing of another
    connection reaches either."""
    rebound0 = METRICS.get("lsp.connects_rebound")
    monkeypatch.setattr(
        lspnet, "create_client_endpoint", holding_second_connect())
    server = lsp.Server(0, params())
    reads = server_reads(server)
    try:
        lspnet.set_server_write_drop_percent(100)
        made = queue.Queue()
        spawn(lambda: made.put(lsp.Client("127.0.0.1", server.port, params())))
        time.sleep(1.5 * EPOCH_S)
        lspnet.set_server_write_drop_percent(0)
        client = made.get(timeout=5)
        client.write(b"job")
        assert reads.get(timeout=5) == (client.conn_id(), b"job")
        lost = reads.get(timeout=5)
        assert isinstance(lost, lsp.ConnLostError)
        assert lost.conn_id == client.conn_id()
        assert METRICS.get("lsp.connects_rebound") - rebound0 == 1
        t0 = time.monotonic()
        with pytest.raises(lsp.ConnLostError):
            client.read(timeout=20 * EPOCH_S)
        assert time.monotonic() - t0 < 10 * EPOCH_S
        client.close()
    finally:
        server.close()
