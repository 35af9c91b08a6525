"""TLS transport + ACL username auth (VERDICT r2 #4; reference:
client/handler/RedisChannelInitializer.java:110-219 SSL pipeline,
BaseConnectionHandler.java:59-122 AUTH user pass)."""
import socket
import ssl
import subprocess

import pytest

from redisson_tpu.client.remote import RemoteRedisson
from redisson_tpu.harness import ClusterRunner
from redisson_tpu.net.client import Connection, client_ssl_context
from redisson_tpu.net.resp import RespError
from redisson_tpu.server.migration import migrate_slots
from redisson_tpu.server.server import ServerThread
from redisson_tpu.utils.crc16 import calc_slot


@pytest.fixture(scope="module")
def certs(tmp_path_factory):
    """Self-signed cert with SANs for localhost/127.0.0.1 (openssl CLI)."""
    d = tmp_path_factory.mktemp("tls")
    cert, key = str(d / "cert.pem"), str(d / "key.pem")
    subprocess.run(
        [
            "openssl", "req", "-x509", "-newkey", "rsa:2048",
            "-keyout", key, "-out", cert, "-days", "2", "-nodes",
            "-subj", "/CN=localhost",
            "-addext", "subjectAltName=DNS:localhost,IP:127.0.0.1",
        ],
        check=True,
        capture_output=True,
    )
    return cert, key


@pytest.fixture()
def tls_server(certs):
    cert, key = certs
    with ServerThread(port=0, tls_cert_file=cert, tls_key_file=key) as st:
        yield st, cert


def test_a_tls_connection_serves_a_request_larger_than_one_old_read(tls_server):
    """The read loop takes up to _FRAME_CAP of the stream's buffer a frame
    (ISSUE 29), under TLS as on a plain socket: a pipelined request larger
    than one old 64 KiB read is answered whole and in order."""
    st, _cert = tls_server
    cmds = []
    for i in range(100):
        cmds += [("SET", f"tls:{i % 7}", "%06d" % i + "v" * 900), ("GET", f"tls:{i % 7}")]
    with st.client() as conn:
        replies = conn.execute_many(cmds, timeout=60.0)
    assert [bytes(r) for r in replies[1::2]] == [c[2].encode() for c in cmds[0::2]]


def test_tls_handshake_and_commands(tls_server):
    st, cert = tls_server
    ctx = client_ssl_context(ca_file=cert)  # verify_hostname default ON
    client = RemoteRedisson(st.address, ssl_context=ctx, timeout=30.0)
    try:
        assert st.address.startswith("tpus://")
        b = client.get_bucket("tls:key")
        b.set("secure")
        assert b.get() == "secure"
    finally:
        client.shutdown()


def test_tls_pubsub_connection(tls_server):
    st, cert = tls_server
    ctx = client_ssl_context(ca_file=cert)
    client = RemoteRedisson(st.address, ssl_context=ctx, timeout=30.0)
    try:
        got = []
        topic = client.get_topic("tls:topic")
        topic.add_listener(lambda ch, msg: got.append(msg))
        import time

        deadline = time.time() + 10
        while not got and time.time() < deadline:
            topic.publish("over-tls")
            time.sleep(0.1)
        assert got and got[0] == "over-tls"
    finally:
        client.shutdown()


def test_plaintext_client_rejected_by_tls_server(tls_server):
    st, _cert = tls_server
    with pytest.raises((ConnectionError, TimeoutError, RespError)):
        Connection(st.server.host, st.server.port, timeout=2.0).execute("PING")


def test_untrusted_ca_rejected(tls_server):
    st, _cert = tls_server
    ctx = ssl.create_default_context()  # system roots: our self-signed fails
    with pytest.raises((ssl.SSLError, ConnectionError, OSError)):
        Connection(st.server.host, st.server.port, ssl_context=ctx, timeout=2.0)


def test_hostname_verification_enforced(tls_server, certs):
    """A cert without a matching SAN must fail when endpoint identification
    is on (sslEnableEndpointIdentification analog) and pass when off."""
    st, cert = tls_server
    ctx = client_ssl_context(ca_file=cert, verify_hostname=True)
    with pytest.raises((ssl.SSLCertVerificationError, ConnectionError, OSError)):
        Connection(
            st.server.host, st.server.port, ssl_context=ctx,
            ssl_hostname="wrong.example.com", timeout=2.0,
        )
    loose = client_ssl_context(ca_file=cert, verify_hostname=False)
    c = Connection(
        st.server.host, st.server.port, ssl_context=loose,
        ssl_hostname="wrong.example.com", timeout=2.0,
    )
    assert c.execute("PING") in (b"PONG", "PONG", "+PONG")
    c.close()


def test_cluster_over_tls_with_migration(certs):
    """The VERDICT done-bar: a cluster test passing over TLS — including a
    live slot migration, whose inter-node drain link must speak TLS too."""
    cert, key = certs
    runner = ClusterRunner(
        masters=2, tls_cert_file=cert, tls_key_file=key, tls_ca_file=cert
    ).run()
    try:
        ctx = client_ssl_context(
            ca_file=cert, cert_file=cert, key_file=key, verify_hostname=False
        )
        client = runner.client(scan_interval=0, ssl_context=ctx)
        for i in range(40):
            client.get_bucket(f"tlsc-{i}").set(f"v{i}")
        for i in range(40):
            assert client.get_bucket(f"tlsc-{i}").get() == f"v{i}"
        # migrate master0's busiest slots while TLS is on everywhere
        lo0, hi0 = runner.slot_ranges[0]
        mine = [f"tlsc-{i}" for i in range(40) if lo0 <= calc_slot(f"tlsc-{i}".encode()) <= hi0]
        slots = sorted({calc_slot(n.encode()) for n in mine})
        moved = migrate_slots(
            runner.masters[0].address, runner.masters[1].address, slots,
            ssl_context=ctx,
        )
        assert moved >= len(mine) * 0.9
        client.refresh_topology()
        for i in range(40):
            assert client.get_bucket(f"tlsc-{i}").get() == f"v{i}"
        client.shutdown()
    finally:
        runner.shutdown()


# -- ACL ----------------------------------------------------------------------


def test_acl_username_auth():
    with ServerThread(port=0, password="rootpw", users={"alice": "apw"}) as st:
        host, port = st.server.host, st.server.port
        # no auth -> NOAUTH gate
        c = Connection(host, port)
        reply = c.execute("GET", "x")
        assert isinstance(reply, RespError) and "NOAUTH" in str(reply)
        c.close()
        # AUTH user pass (ACL form)
        c = Connection(host, port, username="alice", password="apw")
        assert not isinstance(c.execute("SET", "acl:k", "v"), RespError)
        c.close()
        # default-user password still works
        c = Connection(host, port, password="rootpw")
        assert bytes(c.execute("GET", "acl:k")) == b"v"
        c.close()
        # wrong ACL password -> WRONGPASS at handshake
        with pytest.raises(RespError, match="WRONGPASS"):
            Connection(host, port, username="alice", password="bad")
        # unknown user -> WRONGPASS
        with pytest.raises(RespError, match="WRONGPASS"):
            Connection(host, port, username="mallory", password="apw")


def test_acl_users_without_default_password_still_gate():
    """ACL users alone (no default password) must still require auth."""
    with ServerThread(port=0, users={"bob": "bpw"}) as st:
        host, port = st.server.host, st.server.port
        c = Connection(host, port)
        reply = c.execute("GET", "x")
        assert isinstance(reply, RespError) and "NOAUTH" in str(reply)
        c.close()
        c = Connection(host, port, username="bob", password="bpw")
        assert not isinstance(c.execute("SET", "k", "v"), RespError)
        c.close()


def test_acl_username_through_client_facade():
    from redisson_tpu.config import Config

    with ServerThread(port=0, password="rootpw", users={"svc": "spw"}) as st:
        cfg = Config()
        ssc = cfg.use_single_server()
        ssc.username, ssc.password = "svc", "spw"
        client = RemoteRedisson(st.address, config=cfg, timeout=30.0)
        try:
            client.get_bucket("acl:facade").set("yes")
            assert client.get_bucket("acl:facade").get() == "yes"
        finally:
            client.shutdown()
