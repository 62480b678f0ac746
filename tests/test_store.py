"""Tests for the rendezvous KV store."""

import threading
import time

import pytest

from torchft_tpu.comm.store import (
    PrefixStore,
    StoreClient,
    StoreServer,
    create_store_client,
)


@pytest.fixture()
def store():
    server = StoreServer()
    client = StoreClient(server.addr)
    yield server, client
    client.close()
    server.shutdown()


def test_set_get(store) -> None:
    _, client = store
    client.set("a", b"1")
    assert client.get("a") == b"1"
    assert client.get("missing") is None


def test_wait_blocks_until_set(store) -> None:
    server, client = store
    other = StoreClient(server.addr)

    def _setter() -> None:
        time.sleep(0.1)
        other.set("k", b"v")

    setter = threading.Thread(target=_setter, daemon=True)
    setter.start()
    # the value, not TimeoutError: the set ended the wait, not its deadline
    assert client.wait("k", timeout=5.0) == b"v"
    setter.join()
    other.close()


def test_wait_timeout(store) -> None:
    _, client = store
    with pytest.raises(TimeoutError):
        client.wait("never", timeout=0.1)


def test_add_atomic(store) -> None:
    server, client = store
    clients = [StoreClient(server.addr) for _ in range(4)]

    def _bump(c: StoreClient) -> None:
        for _ in range(50):
            c.add("ctr", 1)

    threads = [threading.Thread(target=_bump, args=(c,)) for c in clients]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert client.add("ctr", 0) == 200
    for c in clients:
        c.close()


def test_delete_and_list(store) -> None:
    _, client = store
    client.set("p/a", b"1")
    client.set("p/b", b"2")
    client.set("q/c", b"3")
    assert client.list_keys("p/") == ["p/a", "p/b"]
    assert client.delete("p/a")
    assert not client.delete("p/a")
    assert client.list_keys("p/") == ["p/b"]


def test_prefix_store(store) -> None:
    server, client = store
    pre = PrefixStore(client, "torchft/quorum_3/0")
    pre.set("addr", b"127.0.0.1:1234")
    raw = StoreClient(server.addr)
    assert raw.get("torchft/quorum_3/0/addr") == b"127.0.0.1:1234"
    raw.close()


def test_create_store_client_with_prefix(store) -> None:
    server, _ = store
    pre = create_store_client(f"{server.addr}/torchft/7")
    assert isinstance(pre, PrefixStore)
    pre.set("x", b"y")
    plain = create_store_client(server.addr)
    assert plain.get("torchft/7/x") == b"y"


def test_large_value(store) -> None:
    _, client = store
    blob = bytes(range(256)) * 4096  # 1 MiB
    client.set("blob", blob)
    assert client.get("blob") == blob
