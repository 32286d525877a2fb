"""Each configuration file against the published config it cites."""
import pytest

import cells

# published config.json values (internlm/internlm2-20b,
# deepseek-ai/deepseek-llm-7b-base)
PUBLISHED = {
    "internlm2-20b-d8": dict(
        hidden_size=6144, intermediate_size=16384, num_attention_heads=48,
        num_key_value_heads=8, num_hidden_layers=48, vocab_size=92544,
        max_position_embeddings=32768, rms_norm_eps=1e-5, rope_theta=1e6,
        tie_word_embeddings=False, torch_dtype="bfloat16"),
    "deepseek-llm-7b-d10": dict(
        hidden_size=4096, intermediate_size=11008, num_attention_heads=32,
        num_key_value_heads=32, num_hidden_layers=30, vocab_size=102400,
        max_position_embeddings=4096, rms_norm_eps=1e-6, rope_theta=1e4,
        tie_word_embeddings=False, torch_dtype="bfloat16"),
}
HERE = {"internlm2-20b-d8": 8, "deepseek-llm-7b-d10": 10}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_config_matches_published(name):
    conf = cells._load("configs", name)
    pub = PUBLISHED[name]
    changed = {k for k, v in pub.items() if conf[k] != v}
    assert changed == set(conf["reduced"]) == {"num_hidden_layers"}
    assert conf["reduced"]["num_hidden_layers"] == {
        "published": pub["num_hidden_layers"], "here": HERE[name]}
    assert conf["num_hidden_layers"] == HERE[name]
    assert conf["head_dim"] * conf["num_attention_heads"] == \
        conf["hidden_size"]


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_model_config_keeps_every_width(name):
    conf = cells._load("configs", name)
    cfg = cells.family(conf).model_config(conf)
    assert (cfg.d_model, cfg.d_ff, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.vocab_size, cfg.num_layers) == (
        conf["hidden_size"], conf["intermediate_size"],
        conf["num_attention_heads"], conf["num_key_value_heads"],
        conf["head_dim"], conf["vocab_size"], conf["num_hidden_layers"])
    assert cfg.rope_theta == conf["rope_theta"]
    assert cfg.norm_eps == conf["rms_norm_eps"]
    assert not cfg.tie_embeddings and cfg.dtype == "bfloat16"
