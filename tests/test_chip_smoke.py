"""chip_smoke.py's control flow, on the CPU at a tiny size.

The script has no option that shrinks it (a chip run could take one by
accident): its phase functions take their configuration as an argument,
and this test builds a tiny one — interpret-mode kernels and reference
attention are chosen HERE, never by the script. The job phase (a
subprocess gang) is covered by the orchestrator e2e tests.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

import chip_smoke
from kubeflow_tpu.models.bert import bert_tiny
from kubeflow_tpu.models.transformer import TransformerConfig
from kubeflow_tpu.serve.model import BucketSpec

REPO = Path(__file__).resolve().parent.parent

# reference attention in the model; the bare-kernel parity inside the
# train phase still runs the flash kernels, under the interpreter
TINY_BERT = bert_tiny(
    num_layers=1, dtype=jnp.float32, attn_impl="reference",
    interpret_kernels=True,
)
TINY_LM = TransformerConfig(
    vocab_size=96, d_model=32, n_layers=1, n_heads=4, d_ff=64, causal=True,
    max_seq_len=128, dtype=jnp.float32, attn_impl="reference",
    interpret_kernels=True,  # the paged kernel engines run the interpreter
)
TINY_SERVE = chip_smoke.ServePhaseConfig(
    bert=TINY_BERT,
    bert_buckets=BucketSpec(batch_sizes=(1, 2), seq_lens=(16,)),
    lm=TINY_LM, prompt_lens=(5, 40),
    lm_buckets=BucketSpec(batch_sizes=(1,), seq_lens=(16,)),
    prefill_chunk=16, max_new_tokens=4, max_batch=4, max_seq=64,
    chunk_steps=2, page_size=16, kv_pool_tokens=4 * 64,
    parity_prompt_lens=(40,), parity_max_seq=64, parity_chunk_steps=2,
)


def test_train_phase_tiny(devices8):
    out = chip_smoke.phase_train(chip_smoke.TrainPhaseConfig(
        bert=TINY_BERT, batch=8, seq=32, steps=3, kernel_shape=(1, 1, 32, 16),
    ))
    assert len(out["losses"]) == 3 and out["compile_s"] > 0
    assert out["kernel_in_step"] is False  # interpret mode: no Mosaic call
    assert out["flash_S32_parity"]["fwd_max_abs_err"] < 2e-2
    json.dumps(out)  # a phase line must serialise


def test_serve_phase_tiny():
    out = chip_smoke.phase_serve(TINY_SERVE)
    assert out["lm"]["compiles_on_warm_shapes"] == 0
    assert out["lm"]["sse_stream_tokens"] == 4
    # float32 on the CPU: the read paths agree byte for byte
    for cmp in out["read_paths"].values():
        assert cmp["identical"] == cmp["requests"] == 1
    assert out["metrics"]["prefill_pieces"] > 0
    assert out["metrics"]["programs_compiled"] > 0
    json.dumps(out)


def test_kinds_phase_tiny():
    """The mixed-kind routed pattern at a tiny size, float32: the engine's
    tokens are the reference's choices."""
    model = dict(
        chip_smoke.kinds_config(), hidden_size=64, num_attention_heads=4,
        head_dim=32, sliding_window=8, intermediate_size=96,
        moe_intermediate_size=48, vocab_size=128, activation_dtype="float32",
        weight_dtype="float32",
    )
    out = chip_smoke.phase_kinds(chip_smoke.KindsPhaseConfig(
        model=model, program={"interpret_kernels": True},
        prompt_lens=(9, 37), prefill_chunk=16, max_new_tokens=6, max_batch=2,
        max_seq=64, chunk_steps=2, page_size=16,
    ))
    assert out["tokens_rated"] == 12 and out["argmax_share"] == 1.0
    assert out["regret_max"] <= 1e-3
    assert out["moe_assignments_decode"] == 16 * 2 * 5
    assert 0 < out["kv_pages_dead_window"] < out["kv_pages_held"]
    json.dumps(out)


def test_greedy_mismatch_is_explained_and_refused():
    """The near-tie rule: a differing token passes only when the oracle
    holds the two within tolerance — a wrong token does not."""
    import jax

    from kubeflow_tpu.models.transformer import TransformerLM

    params = TransformerLM(TINY_LM).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    prompt, want = [3, 4, 5, 6], [7, 8, 9]
    why = chip_smoke.explain_mismatch(
        TINY_LM, params, prompt, [7, 8, 10], want, pad_to=16
    )
    assert why["step"] == 2 and (why["got"], why["want"]) == (10, 9)
    assert why["top2_gap"] >= 0 and why["logit_std"] > 0
    kw = dict(lm=TINY_LM, params=params, prompts=[prompt], pad_to=16)
    same = chip_smoke.compare_streams("t", got=[want], want=[want],
                                      tie_tol=0.0, **kw)
    assert same == {"requests": 1, "identical": 1, "near_tie_first_steps": []}
    with pytest.raises(RuntimeError, match="beyond a tie"):
        chip_smoke.compare_streams("t", got=[[7, 8, 10]], want=[want],
                                   tie_tol=0.0, **kw)
    tie = chip_smoke.compare_streams("t", got=[[7, 8, 10]], want=[want],
                                     tie_tol=1e9, **kw)
    assert tie["near_tie_first_steps"] == [2]


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_fails_without_a_tpu(monkeypatch, capsys, argv):
    """No CPU carry-on: main() exits non-zero, names the platform it
    found, and prints no result line. (The job phase's worker makes the
    same check in its own process; stubbed here — a subprocess gang.)"""
    monkeypatch.setattr(chip_smoke, "phase_job", lambda cfg: {"stub": True})
    with pytest.raises(SystemExit) as e:
        chip_smoke.main(argv)
    assert "'cpu'" in str(e.value.code) and e.value.code != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_final_line_shape():
    dev = chip_smoke.device_report()
    assert set(dev) == {"platform", "kind", "count"}
    assert dev["platform"] == "cpu" and dev["count"] == 8
    line = json.loads(json.dumps({"ok": True, "device": dev}))
    assert line["ok"] is True and isinstance(line["device"]["kind"], str)


def test_sharded_phase_on_four_host_devices():
    """--chips 4's phase function, in a process that has exactly four
    devices (this one has eight, and Trainer takes them all)."""
    prog = (
        "import json, dataclasses, jax.numpy as jnp, chip_smoke\n"
        "from tests.test_chip_smoke import TINY_BERT, TINY_LM\n"
        "out = chip_smoke.phase_sharded(chip_smoke.ShardedPhaseConfig(\n"
        "    bert=TINY_BERT, lm=TINY_LM, batch=8, seq=32, steps=2,\n"
        "    prompt_lens=(6, 20), prefill_chunk=16, max_new_tokens=4,\n"
        "    max_seq=64,\n"
        "    chunk_steps=2, page_size=16, kv_pool_tokens=4 * 64))\n"
        "print(json.dumps(out))\n"
    )
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO),
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    r = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env=env, cwd=str(REPO), timeout=300,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["train"]["mesh"] == {"fsdp": 2, "model": 2}
    assert out["train"]["placement"]["devices"] == [0, 1, 2, 3]
    assert out["engine"]["placement"]["kv_pool"]["devices"] == [0, 1, 2, 3]
    assert out["engine"]["tokens"]["identical"] == 2
    assert out["engine"]["engine_without_mesh_uses_devices"] == [0]
