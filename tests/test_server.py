"""Wire-protocol tests: a real TCP round trip through the MySQL server and
the client SDK (reference: the protocol layer exercised by any mysql client;
here client and server are both ours, meeting at the socket)."""

import contextlib
import functools
import socket
import struct
import threading

import pytest

from baikaldb_tpu.client.mysql_client import (Connection, MySQLError, Pool,
                                              PreparedStatement)
from baikaldb_tpu.server import mysql_server
from baikaldb_tpu.server.mysql_server import MySQLServer, Packets
from baikaldb_tpu.utils import metrics


@pytest.fixture(scope="module")
def server():
    srv = MySQLServer(port=0).start()
    yield srv
    srv.stop()


def test_connect_ping_quit(server):
    c = Connection(port=server.port)
    assert c.ping()
    c.close()


def test_ddl_dml_select_roundtrip(server):
    c = Connection(port=server.port)
    c.query("CREATE TABLE wire (id BIGINT, name VARCHAR(16), v DOUBLE)")
    r = c.query("INSERT INTO wire VALUES (1,'a',1.5),(2,'b',NULL),(3,NULL,3.0)")
    assert r.affected_rows == 3
    r = c.query("SELECT id, name, v FROM wire ORDER BY id")
    assert r.columns == ["id", "name", "v"]
    assert r.rows[0] == ("1", "a", "1.5")
    assert r.rows[1][2] is None
    assert r.rows[2][1] is None
    r = c.query("SELECT name, COUNT(*) n FROM wire GROUP BY name ORDER BY n DESC, name")
    assert len(r.rows) == 3
    c.close()


def test_error_packet(server):
    c = Connection(port=server.port)
    with pytest.raises(MySQLError):
        c.query("SELECT broken syntax here FROM")
    # connection still usable after an error
    assert c.ping()
    c.close()


def test_use_database(server):
    c = Connection(port=server.port)
    c.query("CREATE DATABASE IF NOT EXISTS wiredb")
    c.query("USE wiredb")
    c.query("CREATE TABLE t2 (x BIGINT)")
    c.query("INSERT INTO t2 VALUES (7)")
    r = c.query("SELECT x FROM t2")
    assert r.rows == [("7",)]
    c.close()


def test_concurrent_connections_share_database(server):
    c1 = Connection(port=server.port)
    c2 = Connection(port=server.port)
    c1.query("CREATE TABLE shared (x BIGINT)")
    c1.query("INSERT INTO shared VALUES (42)")
    r = c2.query("SELECT x FROM shared")
    assert r.rows == [("42",)]
    c1.close()
    c2.close()


def test_transactions_per_connection(server):
    c1 = Connection(port=server.port)
    c1.query("CREATE TABLE wtx (x BIGINT)")
    c1.query("INSERT INTO wtx VALUES (1)")
    c1.query("BEGIN")
    c1.query("INSERT INTO wtx VALUES (2)")
    c1.query("ROLLBACK")
    r = c1.query("SELECT COUNT(*) FROM wtx")
    assert r.rows == [("1",)]
    c1.close()


def test_pool(server):
    pool = Pool("127.0.0.1", server.port, size=2)
    pool.query("CREATE TABLE pooled (x BIGINT)")
    pool.query("INSERT INTO pooled VALUES (1)")
    results = []

    def worker():
        results.append(pool.query("SELECT COUNT(*) FROM pooled").rows[0][0])

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == ["1"] * 6


# -- the framing: one response, one send --------------------------------------
#
# Packets frames into and out of buffers and touches the socket once a
# response.  What is on the socket must be what a send a packet puts there.

class PlainPackets:
    """The plain framer: one ``sendall`` a packet, two ``recv`` a packet."""

    def __init__(self, sock):
        self.sock = sock
        self.seq = 0

    def read(self):
        hdr = self._recvn(4)
        if hdr is None:
            return None
        self.seq = (hdr[3] + 1) & 0xFF
        return self._recvn(hdr[0] | (hdr[1] << 8) | (hdr[2] << 16))

    def _recvn(self, n):
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                return None
            buf += chunk
        return buf

    def write(self, payload):
        while True:
            part, payload = payload[:0xFFFFFF], payload[0xFFFFFF:]
            self.sock.sendall(frame(part, self.seq))
            self.seq = (self.seq + 1) & 0xFF
            if len(part) < 0xFFFFFF:
                break

    def flush(self):
        pass

    def reset(self):
        self.seq = 0


def frame(payload: bytes, seq: int = 0) -> bytes:
    return struct.pack("<I", len(payload))[:3] + bytes([seq]) + payload


@contextlib.contextmanager
def framer(cls):
    """Connections accepted meanwhile are framed by ``cls``."""
    old, mysql_server.Packets = mysql_server.Packets, cls
    try:
        yield
    finally:
        mysql_server.Packets = old


class CountingSock:
    """A socket that notes each ``sendall`` and ``recv`` before it returns."""

    def __init__(self, sock, calls: list):
        self._sock, self.calls = sock, calls

    def sendall(self, data):
        self.calls.append(("sendall", len(data)))
        return self._sock.sendall(data)

    def recv(self, n):
        data = self._sock.recv(n)
        self.calls.append(("recv", len(data)))
        return data

    def __getattr__(self, name):
        return getattr(self._sock, name)


def counted(calls: list):
    class Counted(Packets):
        def __init__(self, sock):
            super().__init__(CountingSock(sock, calls))
    return Counted


def count(calls: list, what: str) -> int:
    return sum(1 for name, _ in calls if name == what)


class Raw:
    """A client that speaks bytes: what it reads is what was on the socket.
    The constructor reads the greeting and sends the login; its answer is
    the first thing left to read."""

    def __init__(self, port, user="root", auth=b"", database=""):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=30)
        self.buf = b""
        self.take()                     # the greeting
        caps = 0x00000200 | 0x00008000 | 0x00000001 | (8 if database else 0)
        login = (struct.pack("<II", caps, 1 << 24) + bytes([0x21]) +
                 b"\x00" * 23 + user.encode() + b"\x00" +
                 bytes([len(auth)]) + auth)
        if database:
            login += database.encode() + b"\x00"
        self.sock.sendall(frame(login, 1))

    def take(self) -> bytes:
        """One packet as it came, header and payload."""
        while len(self.buf) < 4 or len(self.buf) < 4 + int.from_bytes(
                self.buf[:3], "little"):
            chunk = self.sock.recv(65536)
            assert chunk, "the stream ended inside a response"
            self.buf += chunk
        ln = 4 + int.from_bytes(self.buf[:3], "little")
        pkt, self.buf = self.buf[:ln], self.buf[ln:]
        return pkt

    def response(self, prepare: bool = False) -> bytes:
        """The bytes of one whole response: OK, ERR, COM_STMT_PREPARE's OK
        with its parameter definitions, or a result set up to its second
        EOF."""
        first = got = self.take()
        if first[4] == 0xFF or (first[4] == 0x00 and not prepare):
            return got
        if prepare:
            nparams = struct.unpack_from("<H", first, 4 + 7)[0]
            for _ in range(nparams + (1 if nparams else 0)):
                got += self.take()
            return got
        for _ in range(2):              # column definitions, then rows
            while True:
                pkt = self.take()
                got += pkt
                if pkt[4] == 0xFE and len(pkt) < 4 + 9:
                    break
        return got

    def send(self, *commands: bytes):
        """Every command in ONE segment, each from sequence id 0."""
        self.sock.sendall(b"".join(frame(c) for c in commands))

    def rest(self) -> bytes:
        """Everything up to the server's close."""
        out, self.buf = self.buf, b""
        while True:
            chunk = self.sock.recv(65536)
            if not chunk:
                return out
            out += chunk

    def close(self):
        self.sock.close()


def packets_of(raw: bytes) -> list:
    """(sequence id, payload) of every packet in ``raw``."""
    out, pos = [], 0
    while pos < len(raw):
        ln = int.from_bytes(raw[pos:pos + 3], "little")
        out.append((raw[pos + 3], raw[pos + 4:pos + 4 + ln]))
        pos += 4 + ln
    assert pos == len(raw)
    return out


def stmt_execute(sid: int, n: int) -> bytes:
    """COM_STMT_EXECUTE of statement ``sid`` with one BIGINT parameter."""
    return (b"\x17" + struct.pack("<IBI", sid, 0, 1) + b"\x00\x01" +
            struct.pack("<H", 8) + struct.pack("<q", n))


@pytest.fixture(scope="module")
def rows300(server):
    c = Connection(port=server.port)
    c.query("CREATE TABLE fr (id BIGINT, name VARCHAR(32), v DOUBLE, "
            "PRIMARY KEY (id))")
    c.query("INSERT INTO fr VALUES " + ",".join(
        f"({i}, {'NULL' if i % 7 == 0 else repr('name-%d' % i)}, {i}.5)"
        for i in range(1, 301)))
    c.close()
    return server


PREPARE = b"\x16SELECT id, name, v FROM fr WHERE id <= ? ORDER BY id"
# name -> [(command, is a prepare)]; every connection's first statement id
# is 1
EXCHANGES = {
    "ok": [(b"\x03BEGIN", False), (b"\x03COMMIT", False)],
    "ping": [(b"\x0e", False)],
    "err": [(b"\x03SELECT broken syntax here FROM", False)],
    "unknown_command": [(b"\x99", False)],
    "text_1_row": [(b"\x03SELECT id, name, v FROM fr WHERE id = 7", False)],
    # 306 packets: the sequence id wraps
    "text_300_rows": [(b"\x03SELECT id, name, v FROM fr ORDER BY id", False)],
    "prepare": [(PREPARE, True)],
    "binary_1_row": [(PREPARE, True), (stmt_execute(1, 1), False)],
    "binary_300_rows": [(PREPARE, True), (stmt_execute(1, 300), False)],
    "binary_err": [(stmt_execute(9, 1), False)],
}


@pytest.mark.parametrize("name", list(EXCHANGES))
def test_bytes_on_the_socket_are_the_plain_framers(rows300, name):
    with framer(PlainPackets):
        plain = Raw(rows300.port)
    buffered = Raw(rows300.port)
    for raw in (plain, buffered):
        assert packets_of(raw.response())[0][1][0] == 0x00     # login OK
    for command, prepare in EXCHANGES[name]:
        answers = []
        for raw in (plain, buffered):
            raw.send(command)
            answers.append(raw.response(prepare))
        want, got = answers
        assert got == want
        # and the plain framer's are what the protocol says: sequence ids
        # from 1, wrapping at 256
        seqs = [seq for seq, _ in packets_of(want)]
        assert seqs == [(1 + i) & 0xFF for i in range(len(seqs))]
    if name.endswith("300_rows"):
        assert len(packets_of(want)) == 306
    plain.close()
    buffered.close()


class Recorder:
    """Stands where a socket would: keeps every ``sendall``."""

    def __init__(self):
        self.sends = []

    def sendall(self, data):
        self.sends.append(bytes(data))


@pytest.mark.parametrize("size", [0, 1, 0xFFFFFE, 0xFFFFFF, 0x1000000],
                         ids=["empty", "1", "16m-1", "16m", "16m+1"])
def test_a_payload_splits_at_16m_as_the_plain_framers(size):
    payload = bytes(size % 251 for _ in range(min(size, 4096)))
    payload = (payload * (size // max(len(payload), 1) + 1))[:size]
    plain, buffered = Recorder(), Recorder()
    PlainPackets(plain).write(payload)
    p = Packets(buffered)
    p.write(payload)
    p.flush()
    assert b"".join(buffered.sends) == b"".join(plain.sends)
    assert len(plain.sends) == (2 if size >= 0xFFFFFF else 1)
    # past the high-water mark the buffer goes out before the flush
    assert len(buffered.sends) == (1 if size < mysql_server.FLUSH_BYTES
                                   else len(plain.sends))


RANGE_100 = "SELECT id, name, v FROM fr WHERE id BETWEEN 101 AND 200"


@pytest.mark.parametrize("how,rows", [("text_point", 1), ("text_range", 100),
                                      ("binary_range", 100)])
def test_one_result_is_one_send(rows300, how, rows):
    served, client = [], []
    with framer(counted(served)):
        c = Connection(port=rows300.port)
    c.p.sock = CountingSock(c.sock, client)
    if how == "binary_range":
        sid = c.prepare("SELECT id, name, v FROM fr WHERE id > ? LIMIT 100")
        run = functools.partial(c.execute, sid, (100,))
    elif how == "text_range":
        run = functools.partial(c.query, RANGE_100)
    else:
        run = functools.partial(c.query, "SELECT name FROM fr WHERE id = 5")
    run()                                               # compiled, cached
    del served[:], client[:]
    assert len(run().rows) == rows
    # the server read one command and wrote one response; its next recv
    # is still waiting
    assert served[0][0] == "recv" and count(served, "recv") == 1
    assert count(served, "sendall") == 1
    assert count(client, "sendall") == 1
    assert 1 <= count(client, "recv") <= 3
    # every byte the server sent was taken, none twice
    assert sum(n for what, n in client if what == "recv") == \
        sum(n for what, n in served if what == "sendall")
    c.close()


def test_result_past_the_high_water_mark_arrives_whole(server):
    c = Connection(port=server.port)
    c.query("CREATE TABLE big (id BIGINT, body VARCHAR(40000), "
            "PRIMARY KEY (id))")
    bodies = {i: chr(97 + i % 26) * 30000 + str(i) for i in range(80)}
    for lo in range(0, 80, 20):
        c.query("INSERT INTO big VALUES " + ",".join(
            f"({i}, '{bodies[i]}')" for i in range(lo, lo + 20)))
    c.close()
    served = []
    with framer(counted(served)):
        c = Connection(port=server.port)
    del served[:]
    r = c.query("SELECT id, body FROM big ORDER BY id")
    assert [(int(i), b) for i, b in r.rows] == sorted(bodies.items())
    sends = [n for what, n in served if what == "sendall"]
    assert sum(sends) > 2 * mysql_server.FLUSH_BYTES
    # each send but the last left as the buffer passed the mark: whole
    # packets, so within one row of it
    assert len(sends) == 3
    assert all(mysql_server.FLUSH_BYTES <= n < mysql_server.FLUSH_BYTES + 40000
               for n in sends[:-1])
    c.close()


@pytest.mark.parametrize("case", ["raw_ping_then_query",
                                  "stmt_close_rides_with_the_next_command"])
def test_two_commands_in_one_segment_get_two_answers(rows300, case):
    if case == "raw_ping_then_query":
        raw = Raw(rows300.port)
        raw.response()
        raw.send(b"\x0e", b"\x03SELECT id FROM fr WHERE id = 9",
                 b"\x03SELECT nope FROM fr")
        ok, result, err = raw.response(), raw.response(), raw.response()
        assert packets_of(ok) == [(1, packets_of(ok)[0][1])]
        assert packets_of(ok)[0][1][0] == 0x00
        assert packets_of(result)[3] == (4, b"\x019")          # the row
        assert packets_of(err)[0][1][0] == 0xFF
        raw.close()
        return
    c = Connection(port=rows300.port)
    sent = []
    c.p.sock = CountingSock(c.sock, sent)
    st = PreparedStatement(c, "SELECT id FROM fr WHERE id = ?")
    assert st.execute((3,)).rows == [("3",)]
    del sent[:]
    st.close()                          # COM_STMT_CLOSE has no answer ...
    assert count(sent, "sendall") == 0
    assert c.query("SELECT id FROM fr WHERE id = 4").rows == [("4",)]
    assert count(sent, "sendall") == 1  # ... and left with the next command
    with pytest.raises(MySQLError) as ei:
        c.execute(st.sid, (3,))
    assert ei.value.code == 1243        # the server did free it
    c.close()


@pytest.mark.parametrize("case,code", [("wrong_password", 1045),
                                       ("unknown_database", 1049),
                                       ("unknown_command_then_quit", 1047),
                                       ("statement_error_then_quit", 1064)])
def test_err_packet_is_delivered_before_the_close(server, case, code):
    if case == "wrong_password":
        raw = Raw(server.port, auth=b"x" * 20)
    elif case == "unknown_database":
        raw = Raw(server.port, database="no_such_database")
    else:
        raw = Raw(server.port)
        assert raw.response()[4] == 0x00
        raw.send(b"\x99" if case.startswith("unknown") else b"\x03SELEC 1",
                 b"\x01")
    (seq, err), = packets_of(raw.rest())          # then the stream ended
    assert err[0] == 0xFF
    assert struct.unpack_from("<H", err, 1)[0] == code
    assert seq == (1 if case.endswith("quit") else 2)
    raw.close()


def test_wire_counters_of_a_point_read(rows300):
    c = Connection(port=rows300.port)
    sql = "SELECT name FROM fr WHERE id = 11"
    assert c.query(sql).rows == [("name-11",)]
    names = ("wire_packets", "wire_sends", "wire_recvs")
    before = {name: getattr(metrics, name).value for name in names}
    assert c.query(sql).rows == [("name-11",)]
    grew = {name: getattr(metrics, name).value - before[name]
            for name in names}
    # both ends of the wire are this process: the command (1 packet, 1 send,
    # the server's 1 recv) and the result (column count, definition, EOF,
    # row, EOF: 5 packets, 1 send, the client's 1 recv)
    assert grew == {"wire_packets": 6, "wire_sends": 2, "wire_recvs": 2}
    status = {r[0]: r[1] for r in c.query("SHOW STATUS").rows}
    assert int(status["wire_sends.value"]) >= 2
    c.close()
