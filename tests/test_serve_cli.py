"""launch/serve.py end-to-end smoke (sim mode) + stable JSON schema."""
import json

import pytest

from repro.launch import serve

REQUIRED_KEYS = {
    "schema_version", "policy", "arch", "mode", "rate", "workers", "seed",
    "n_total", "n_finished", "slo_attainment", "ttft_attainment",
    "tpot_attainment", "ttft_avg", "ttft_p90", "tpot_avg", "tpot_p90",
    "queue_avg", "queue_p90", "blocked_time_avg", "migrations", "restarts",
    "preemptions", "migration_wait_avg", "weighted_attainment",
    "per_class", "scenario",
    # v3: tiered-KV + prefix-reuse counters
    "kv_offloads", "kv_restores", "pages_offloaded", "pages_restored",
    "pages_reprefilled", "prefix_lookups", "prefix_hits", "prefix_hit_rate",
}


def _run(extra=()):
    return serve.main(["--mode", "sim", "--rate", "1.0",
                       "--duration", "15", "--json", *extra])


def test_serve_sim_json_schema(capsys):
    row = _run(["--seed", "1"])
    out = capsys.readouterr().out
    data = json.loads(out)          # stdout is exactly one JSON object
    # v3: tiered-KV + prefix-reuse counters (additive over the v2
    # per_class/weighted_attainment layout)
    assert data["schema_version"] == serve.METRICS_SCHEMA_VERSION == 3
    assert REQUIRED_KEYS <= set(data)
    assert data["mode"] == "sim" and data["seed"] == 1
    # both features default OFF: counters exist but must read zero
    assert data["kv_offloads"] == 0 and data["prefix_lookups"] == 0
    assert data["prefix_hit_rate"] == 0.0
    assert data["n_total"] > 0
    assert data["n_finished"] == data["n_total"]
    assert row["n_total"] == data["n_total"]
    # transfer engine on by default -> migration accounting present
    assert "kv_bytes_migrated" in data and "transfer_seconds" in data
    # single-class default run: one 'default' class, weighted == aggregate
    assert set(data["per_class"]) == {"default"}
    assert data["weighted_attainment"] == pytest.approx(
        data["slo_attainment"])


def test_serve_seed_reproducible(capsys):
    a = _run(["--seed", "5"])
    b = _run(["--seed", "5"])
    c = _run(["--seed", "6"])
    capsys.readouterr()
    assert a == b
    strip = lambda row: {k: v for k, v in row.items() if k != "seed"}
    assert strip(a) != strip(c)


def test_serve_online_predictor_flag(capsys):
    row = _run(["--online-predictor"])
    capsys.readouterr()
    assert "predictor_prefill_scale" in row
    assert "predictor_decode_scale" in row
    assert "role_transitions" in row      # windowed rebalancer active


def test_serve_rejects_bad_link_flags(capsys):
    with pytest.raises(SystemExit):
        serve.main(["--ici-bw", "0"])
    with pytest.raises(SystemExit):
        serve.main(["--ici-links", "-1"])
    with pytest.raises(SystemExit):
        serve.main(["--page-size", "0"])
    capsys.readouterr()


def test_serve_slo_classes_per_class_metrics(capsys):
    row = _run(["--slo-classes",
                "interactive:ttft=1.0,tpot=0.05,weight=2,frac=0.6;"
                "batch:ttft=12,tpot=0.6,frac=0.4"])
    capsys.readouterr()
    assert row["scenario"] == "slo-classes"
    assert set(row["per_class"]) == {"interactive", "batch"}
    assert row["per_class"]["interactive"]["weight"] == 2.0
    n = sum(c["n_total"] for c in row["per_class"].values())
    assert n == row["n_total"] > 0
    # weighted attainment is the weight-normalised per-class combination
    want = sum(c["weight"] * c["slo_attainment"]
               for c in row["per_class"].values()) \
        / sum(c["weight"] for c in row["per_class"].values())
    assert row["weighted_attainment"] == pytest.approx(want)


def test_serve_named_scenario(capsys):
    row = _run(["--scenario", "mixture", "--duration", "10"])
    capsys.readouterr()
    assert row["scenario"] == "mixture"
    assert set(row["per_class"]) == {"interactive", "batch"}


def test_serve_trace_csv_replay(tmp_path, capsys):
    path = tmp_path / "trace.csv"
    path.write_text("timestamp_ms,input_length,output_length,slo_class\n"
                    "0,512,16,interactive\n"
                    "500,2048,32,batch\n"
                    "900,256,8,interactive\n")
    row = _run(["--trace-csv", str(path), "--slo-classes",
                "interactive:ttft=2.0,tpot=0.1;batch:ttft=20,tpot=1.0"])
    capsys.readouterr()
    assert row["n_total"] == 3
    assert row["per_class"]["interactive"]["n_total"] == 2
    assert row["per_class"]["batch"]["n_total"] == 1


def test_serve_rejects_bad_scenario_and_classes(capsys):
    with pytest.raises(SystemExit):
        serve.main(["--scenario", "nope"])
    with pytest.raises(SystemExit):
        serve.main(["--slo-classes", "broken"])
    with pytest.raises(SystemExit):
        serve.main(["--slo-classes", "a:ttft=1"])       # missing tpot
    with pytest.raises(SystemExit):
        serve.main(["--slo-classes", "a:ttft=1,tpot=-2"])
    with pytest.raises(SystemExit):     # fracs oversubscribe the rate
        serve.main(["--slo-classes",
                    "a:ttft=1,tpot=0.1,frac=0.8;b:ttft=2,tpot=0.2,frac=0.8"])
    with pytest.raises(SystemExit):     # unassigned class left zero traffic
        serve.main(["--slo-classes",
                    "a:ttft=1,tpot=0.1,frac=1.0;b:ttft=2,tpot=0.2"])
    with pytest.raises(SystemExit):     # --slo-classes owns the workload
        serve.main(["--scenario", "agentic",
                    "--slo-classes", "a:ttft=1,tpot=0.1"])
    with pytest.raises(SystemExit):     # duplicate class names
        serve.main(["--slo-classes", "a:ttft=1,tpot=0.1;a:ttft=2,tpot=0.2"])
    with pytest.raises(SystemExit):     # absolute + scale conflict
        serve.main(["--slo-classes", "a:ttft=1,scale=5"])
    capsys.readouterr()


def test_serve_real_mode_serves_prompts_past_the_old_cap(monkeypatch,
                                                          tmp_path):
    """--mode real runs the published config unless --smoke; the cache
    geometry comes from --max-slots/--max-len and every request is
    clipped to fit one slot, not to the former 48-token prompt cap."""
    from repro.launch import serve

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    row = serve.main(["--mode", "real", "--smoke", "--arch", "qwen2-1.5b",
                      "--policy", "tropical", "--rate", "2",
                      "--duration", "6", "--workers", "2",
                      "--max-slots", "8", "--max-len", "256"])
    assert row["arch"] == "qwen2-smoke" and row["platform"] == "cpu"
    assert row["n_finished"] == row["n_total"] > 0
    assert row["max_prompt_len"] > 48
    assert row["max_slots"] == 8 and row["max_len"] == 256
    assert row["generated_tokens"] >= row["output_tokens"] > 0
    assert row["prefill_traces"] >= 1 and row["decode_traces"] == 1


def test_clip_to_cache_fits_one_slot():
    from repro.core.request import Request, SLOSpec
    from repro.launch.serve import clip_to_cache

    slo = SLOSpec(ttft=1.0, tpot=0.1)
    reqs = [Request(rid=0, arrival_time=0.0, prompt_len=5000,
                    output_len=900, slo=slo),
            Request(rid=1, arrival_time=0.0, prompt_len=40, output_len=8,
                    slo=slo)]
    clip_to_cache(reqs, 1024)
    assert (reqs[0].prompt_len, reqs[0].output_len) == (512, 512)
    assert (reqs[1].prompt_len, reqs[1].output_len) == (40, 8)
