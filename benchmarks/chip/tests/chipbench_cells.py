"""What the benchmark's tests share: the repository root on ``sys.path``
(so that they import the benchmark as ``benchmarks.chip``) and the cells
at a size a CPU test run can hold.  A plain module, not a ``conftest``,
so that its name cannot shadow the repository's own test helpers."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def tiny_train_cell():
    """The training cell's files at a size a CPU test run can hold; the
    vocabulary is wide enough that the float8 control's flushed gradient
    entries show, as they do at the cell's own size."""
    from benchmarks.chip import harness
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, "train.opt-125m.s2048")
    cell.config = dict(cell.config, hidden_size=64, num_hidden_layers=2,
                       num_attention_heads=4, ffn_dim=128, vocab_size=8192,
                       max_position_embeddings=256)
    cell.traffic = dict(cell.traffic, batch=2, seq_len=128)
    return cell


def tiny_gqa_train_cell():
    """The training cell run on a configuration of the other decoder
    the reference and ``families/decoder.py`` cover (Qwen2's layout:
    RMSNorm, rotary positions, grouped-query attention with q/k/v biases,
    a SwiGLU MLP, an untied head), at a size a CPU test run can hold."""
    cell = tiny_train_cell()
    cell.config = {
        "name": "gqa-tiny", "source": "https://huggingface.co/Qwen/Qwen2-7B",
        "family": "decoder",
        "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 8192, "max_position_embeddings": 256,
        "rope_theta": 1_000_000.0, "qkv_bias": True,
        "tie_word_embeddings": False, "activation_function": "silu",
        "norm": "rmsnorm", "position_embedding": "rope",
        "layer_norm_eps": 1e-6, "param_dtype": "float32",
        "compute_dtype": "bfloat16"}
    return cell
