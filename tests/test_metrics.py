"""/metrics endpoint: Prometheus exposition of engine scheduler state
(SURVEY §5.5 — the reference exports no metrics at all)."""

from tests.conftest import make_client


def _config():
    return {
        "settings": {"timeout": 60},
        "primary_backends": [
            {"name": "LLM1", "url": "tpu://llama-tiny?seed=9107&slots=2", "model": "t"},
        ],
    }


async def test_metrics_exposition():
    async with make_client(_config()) as client:
        before = (await client.get("/metrics")).text
        assert "quorum_tpu_uptime_seconds" in before
        assert 'quorum_tpu_engine_slots{backend="LLM1"} 2' in before
        assert 'quorum_tpu_engine_requests_total{backend="LLM1"} 0' in before
        # members is exported as a gauge (1 on ordinary engines; M on
        # stacked engines, whose "slots" reads M x n_slots flat rows)
        assert 'quorum_tpu_engine_members{backend="LLM1"} 1' in before
        assert "# TYPE quorum_tpu_engine_members gauge" in before
        # round-3 counters, typed as counters in the exposition
        for key in ("cancellations_total",):
            assert f"# TYPE quorum_tpu_engine_{key} counter" in before
            assert f'quorum_tpu_engine_{key}{{backend="LLM1"}} 0' in before

        resp = await client.post(
            "/v1/chat/completions",
            json={"model": "t", "messages": [{"role": "user", "content": "hi"}],
                  "max_tokens": 5},
            headers={"Authorization": "Bearer x"},
        )
        assert resp.status_code == 200

        after = (await client.get("/v1/metrics")).text
        assert 'quorum_tpu_engine_requests_total{backend="LLM1"} 1' in after
        assert 'quorum_tpu_engine_tokens_total{backend="LLM1"} 5' in after
        assert 'quorum_tpu_engine_busy_slots{backend="LLM1"} 0' in after
        assert 'quorum_tpu_engine_failures_total{backend="LLM1"} 0' in after
        # prometheus text format: TYPE comments present
        assert "# TYPE quorum_tpu_engine_tokens_total counter" in after
        # step-loop occupancy counters (ISSUE 1): decode dispatch turns and
        # the busy-row sum they stepped
        assert "# TYPE quorum_tpu_engine_decode_chunks_total counter" in after
        assert ("# TYPE quorum_tpu_engine_decode_busy_rows_total counter"
                in after)
        # latency histogram families with full exposition triplets
        for fam in ("quorum_tpu_request_duration_seconds",
                    "quorum_tpu_ttft_seconds",
                    "quorum_tpu_inter_token_seconds",
                    "quorum_tpu_queue_wait_seconds"):
            assert f"# TYPE {fam} histogram" in after, fam
            assert f"{fam}_sum" in after, fam
            assert f"{fam}_count" in after, fam
        # request duration carries a status-class label so error floods
        # don't read as latency improvements
        assert ('quorum_tpu_request_duration_seconds_bucket'
                '{status="2xx",le="+Inf"}') in after
        assert 'quorum_tpu_queue_wait_seconds_bucket{le="+Inf"}' in after
