"""Host offload is decided by the platform, not by the memories a device
lists: a CPU device lists ``pinned_host`` too, yet host and device memory
are one space there, so offload is a recorded no-op on CPU and targets
``pinned_host`` on an accelerator."""

import jax
import jax.numpy as jnp

from repro.exec import rowprog


def test_offload_is_noop_on_cpu():
    assert jax.default_backend() == "cpu"
    assert rowprog.offload_is_noop()
    assert rowprog.host_memory_kind() == rowprog.default_memory_kind()


def test_host_placement_is_identity_on_cpu():
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": (jnp.ones(4),)}
    for move in (rowprog.to_host, rowprog.to_device):
        moved = move(tree)
        for before, after in zip(jax.tree.leaves(tree),
                                 jax.tree.leaves(moved)):
            assert after is before
    # under jit too: the traced step carries no placement at all
    text = jax.jit(lambda t: rowprog.to_device(rowprog.to_host(t))) \
        .lower(tree).as_text()
    assert "pinned_host" not in text


def test_offload_targets_pinned_host_on_accelerator(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not rowprog.offload_is_noop()
    assert rowprog.host_memory_kind() == "pinned_host"
    placed = []
    monkeypatch.setattr(jax, "device_put",
                        lambda x, dst: placed.append(dst) or x)
    x = jnp.ones(3)
    rowprog.to_host({"c": x})
    rowprog.to_device([x, x])
    assert placed == [jax.memory.Space.Host, jax.memory.Space.Device,
                      jax.memory.Space.Device]
    assert rowprog.to_host(()) == ()  # no leaves: nothing to move
