"""The benchmark's references and generators, pinned to the program at
small sizes on the CPU: the same seed gives the same telemetry windows and
tokens, the C4D reference gives the program's verdicts and node actions,
and the SmolLM reference starts from the program's weights and follows its
losses."""
import numpy as np
import pytest

from chipbench import streams
from chipbench.harness import HERE, load_json
from chipbench.reference import c4d as ref_c4d
from chipbench.reference import smollm as ref_lm
from chipbench.reference.telemetry import Fault, RingTelemetry

C4D_CFG = load_json(HERE / "configs" / "c4d-fleet-day.json")
MIX = load_json(HERE / "traffic" / "incident_stream.json")
FAULTS = [[], [Fault("slow_src", rank=9, severity=7.5)],
          [Fault("slow_link", link=(20, 21), severity=11.0)],
          [Fault("comm_hang", rank=33)], [Fault("crash", rank=63)],
          [Fault("slow_dst", rank=5), Fault("straggler", rank=40)],
          [Fault("noncomm_hang", rank=2)]]


def _program_window(w):
    from repro.core.c4d.telemetry import CommunicatorInfo, TelemetryArrays
    n = int(max(w.tr_src.max(), w.hb_rank.max())) + 1
    return TelemetryArrays(
        window_id=w.window_id, comms=[CommunicatorInfo(0, n, tuple(range(n)))],
        tr_src=w.tr_src, tr_dst=w.tr_dst, tr_bytes=w.tr_bytes,
        tr_post=w.tr_post, tr_start=w.tr_start, tr_end=w.tr_end,
        hb_rank=w.hb_rank, hb_seq=w.hb_seq, hb_t=w.hb_t,
        op_rank=w.op_rank, op_seq=w.op_seq, t_begin=w.t_begin, t_end=w.t_end)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_generator_matches_program(seed):
    from repro.core import faults as pf
    ours = RingTelemetry(64, seed=seed)
    theirs = pf.RingJobTelemetry(64, seed=seed)
    for wid, fs in enumerate(FAULTS):
        a = ours.window(wid, fs)
        b = theirs.window_arrays(wid, [pf.Fault(f.kind, f.rank, f.link, f.severity)
                                       for f in fs])
        for col in ("tr_src", "tr_dst", "tr_bytes", "tr_post", "tr_start",
                    "tr_end", "hb_rank", "hb_seq", "hb_t", "op_rank", "op_seq"):
            assert np.array_equal(getattr(a, col), getattr(b, col)), (wid, col)
        assert (a.t_begin, a.t_end) == (b.t_begin, b.t_end)


def test_stream_is_a_function_of_the_seed():
    cfg = dict(C4D_CFG, n_ranks=64)
    one = [p for _, p in zip(range(12), streams.episode_stream(cfg, MIX, 9))]
    two = [p for _, p in zip(range(12), streams.episode_stream(cfg, MIX, 9))]
    other = [p for _, p in zip(range(12), streams.episode_stream(cfg, MIX, 10))]
    assert [p.fault for p in one] == [p.fault for p in two]
    assert all(np.array_equal(a.window.tr_end, b.window.tr_end)
               for a, b in zip(one, two))
    assert not np.array_equal(one[0].window.tr_end, other[0].window.tr_end)
    assert [p.window.window_id for p in one] == list(range(12))
    assert sum(p.warmup for p in one) == sum(
        e["fault_free_windows"] + MIX["persist_windows"][e["syndrome"]]
        for e in MIX["warmup"])


@pytest.mark.parametrize("n_ranks", [64, 256])
def test_c4d_reference_matches_program(n_ranks):
    from repro.core.c4d.master import C4DMaster
    cfg = dict(C4D_CFG, n_ranks=n_ranks)
    program = C4DMaster(n_ranks=n_ranks, ranks_per_node=8, backend="numpy")
    ref = ref_c4d.Master(n_ranks, 8, ref_c4d.Thresholds(**cfg["thresholds"]))
    acted = 0
    for planned, _ in zip(streams.episode_stream(cfg, MIX, 3), range(30)):
        acts = program.ingest(_program_window(planned.window))
        got = [(v.syndrome, v.rank, v.link, v.score)
               for v in program.offline_log[-1][1]]
        want, want_acts, _ = ref.ingest(planned.window)
        assert got == want
        assert [(a.node_id, [(v.syndrome, v.rank, v.link) for v in a.verdicts])
                for a in acts] == want_acts
        acted += bool(want_acts)
    assert acted >= 5


SMOKE = dict(load_json(HERE / "configs" / "smollm-135m.json"),
             num_hidden_layers=4, hidden_size=72, num_attention_heads=3,
             num_key_value_heads=3, head_dim=24, intermediate_size=128,
             vocab_size=512)


def _trainer(seed, workdir, batch=4, seq=32):
    from chipbench.drivers.train import program_config
    from repro.train.trainer import Trainer
    cfg = dict(SMOKE, parallel=dict(SMOKE["parallel"], remat="none"))
    mix = {"name": "smoke", "seq_len": seq, "global_batch": batch, "data": 1}
    run, shape = program_config(cfg, mix, seed)
    return cfg, Trainer(run, shape, workdir=str(workdir))


def test_smollm_reference_starts_where_the_program_starts(tmp_path):
    cfg, tr = _trainer(2**31 + 3, tmp_path)
    ours = ref_lm.init_params(cfg, 2**31 + 3)
    p = tr.params
    unit = p["segments"][0]["unit"]["0"]
    pairs = [(p["embed"]["table"], ours["embed"]),
             (p["final_norm"]["scale"], ours["final_norm"]),
             (unit["attn"]["wq"], ours["layers"]["wq"]),
             (unit["attn"]["wk"], ours["layers"]["wk"]),
             (unit["attn"]["wv"], ours["layers"]["wv"]),
             (unit["attn"]["wo"], ours["layers"]["wo"]),
             (unit["mlp"]["wi_gate"], ours["layers"]["gate"]),
             (unit["mlp"]["wi_up"], ours["layers"]["up"]),
             (unit["mlp"]["wo"], ours["layers"]["down"]),
             (unit["ln1"]["scale"], ours["layers"]["ln1"])]
    for a, b in pairs:
        assert np.array_equal(np.asarray(a, np.float32), np.asarray(b))
    for step in range(3):
        assert np.array_equal(tr.pipeline.batch(step)["tokens"],
                              ref_lm.batch_tokens(2**31 + 3, step, 4, 32, 512))


def test_smollm_reference_follows_the_program_losses(tmp_path):
    cfg, tr = _trainer(5, tmp_path)
    report = tr.train(3)
    tr.ckpt.close()
    batches = [ref_lm.batch_tokens(5, s, 4, 32, 512) for s in range(3)]
    want = ref_lm.train_steps(cfg, cfg["optimizer"], 5, batches, rows=1,
                              loss_rows=(2, 4))
    # bfloat16 products in the program against float32 in the reference
    assert np.allclose(report.losses, want["losses"], rtol=1e-3)
    # the step reports its last microbatch's loss, not the batch mean
    full = ref_lm.train_steps(cfg, cfg["optimizer"], 5, batches[:1], rows=4)
    assert full["losses"][0] != pytest.approx(want["losses"][0], rel=1e-4)
