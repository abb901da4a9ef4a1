"""Observability: aggregation log file channel, request ids, phase timing.

Reference behavior being mirrored: dedicated ``aggregation`` logger writing
``logs/aggregation.log`` with a startup test write
(/root/reference/src/quorum/oai_proxy.py:17-37)."""

import logging

from tests.conftest import make_client, two_backend_parallel_config

from quorum_tpu.backends.fake import FakeBackend
from quorum_tpu.observability import RequestTrace, setup_aggregation_log


def test_setup_aggregation_log_writes_file(tmp_path):
    path = setup_aggregation_log(tmp_path / "logs")
    assert path.exists()
    assert "Aggregation logging initialized" in path.read_text()
    # idempotent: second call must not duplicate handlers
    n = len(logging.getLogger("aggregation").handlers)
    setup_aggregation_log(tmp_path / "logs")
    assert len(logging.getLogger("aggregation").handlers) == n


def test_phases_accumulate_per_span_name():
    t = RequestTrace("req-x")
    with t.span("fanout"):
        pass
    with t.span("fanout"):
        pass
    with t.span("combine"):
        pass
    assert set(t.phases) == {"fanout", "combine"}
    assert t.total >= t.phases["fanout"]
    t.log("complete", status=200)  # must not raise


async def test_response_carries_request_id():
    cfg = two_backend_parallel_config()
    client = make_client(
        cfg,
        LLM1=FakeBackend("LLM1", text="a"),
        LLM2=FakeBackend("LLM2", text="b"),
    )
    r = await client.post(
        "/chat/completions",
        json={"model": "m", "messages": [{"role": "user", "content": "q"}]},
        headers={"Authorization": "Bearer k"},
    )
    assert r.status_code == 200
    assert r.headers["x-request-id"].startswith("req-")


def test_setup_aggregation_log_honors_new_directory(tmp_path):
    """A later call with a different dir must attach a handler there, not
    silently keep logging only to the first location."""
    p1 = setup_aggregation_log(tmp_path / "a")
    p2 = setup_aggregation_log(tmp_path / "b")
    assert p1 != p2
    assert p2.exists()
    logging.getLogger("aggregation").info("hello-both")
    assert "hello-both" in p1.read_text()
    assert "hello-both" in p2.read_text()


async def test_max_tokens_zero_rejected_400():
    from quorum_tpu.backends.base import BackendError
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec
    import pytest

    b = TpuBackend.from_spec(BackendSpec(name="T", url="tpu://llama-tiny"))
    with pytest.raises(BackendError) as ei:
        await b.complete(
            {"messages": [{"role": "user", "content": "x"}], "max_tokens": 0}, {}, 30.0
        )
    assert ei.value.status_code == 400
