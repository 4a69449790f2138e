"""The MoE cell (``deepseek-v2-lite-w4.chat-moe``) through the harness end
to end at a tiny size on the CPU, with the Pallas kernels in interpret
mode: set-up, window, the comparison with ``references/deepseek_v2.py``,
and its three per-layer readers on what the window recorded (the peaks of
a v5e and a stand-in trace slice, since a CPU run has no device numbers)."""
import run

SIZES = dict(n_layers=2, vocab=512, d_model=256, d_ff=512, heads=(4, 4))
SEED = 2 ** 31 + 91
CELL = "deepseek-v2-lite-w4.chat-moe"
NEW = ("experts_hit_share.chat-moe", "mfu.decode.chat-moe",
       "expert_gemv_roofline.chat-moe")


def test_moe_serve_cell():
    import roofline

    def hook(job, ctx, ref, arch):
        ctx = {**ctx, "peaks": roofline.peaks("TPU v5 lite"),
               "trace": {"op_s_by_span": {"serve.decode": {"quant_gmm": 1.0}},
                         "op_count_by_span": {"serve.decode":
                                              {"quant_gmm": 3.0}}}}
        return {name: run.reader(name)(ctx) for name in NEW}

    r = run.run_cell(run.cell(CELL), SEED, 3.0, False, sizes=SIZES,
                     hook=hook)
    assert r["correct"], r["check"]
    e2e = {m["name"] for m in run.metric_names(CELL, trace=False)}
    assert set(r["metrics"]) <= e2e and "setup_s" in r["metrics"]
    assert "ttft_p90_ms" in r["metrics"] and r["attempted"] > 0
    got = r["extra"]
    # one MoE layer of 8 experts, 4 slots × top-6 rows: most experts hit
    assert 50 <= got["experts_hit_share.chat-moe"] <= 100, got
    assert got["mfu.decode.chat-moe"] > 0, got
    assert got["expert_gemv_roofline.chat-moe"] > 0, got
