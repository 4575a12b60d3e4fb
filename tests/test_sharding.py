"""Sharding rules: divisibility fallbacks and spec structure (AbstractMesh —
no devices needed)."""
import jax
import numpy as np
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import model as MD
from repro.sharding import rules as RU

SP = AbstractMesh((16, 16), ("data", "model"))
MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def leaves_with_paths(tree):
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, P))[0]


def find(specs, *frags):
    """Match path fragments; a fragment starting with '=' requires an exact
    path-component match (so 'embed' doesn't also hit 'unembed')."""
    out = []
    for path, spec in leaves_with_paths(specs):
        comps = [str(getattr(p, "name", getattr(p, "key", p))) for p in path]
        name = "/".join(comps)
        ok = all((f[1:] in comps) if f.startswith("=") else (f in name)
                 for f in frags)
        if ok:
            out.append((name, spec))
    assert out, frags
    return out


class TestParamSpecs:
    def test_llama3_train_2d(self):
        specs = RU.param_pspecs(SP, MD.schema(get_config("llama3-8b")))
        (_, wq), = find(specs, "blocks", "l0", "attn", "wq")
        assert wq == P(None, "data", "model", None)        # stacked + 2D
        (_, emb), = find(specs, "=embed")
        assert emb == P("model", "data")

    def test_llama4_heads_fall_back_to_replicated(self):
        """40 q-heads % 16 != 0 -> heads dim replicated; FFN still sharded."""
        specs = RU.param_pspecs(SP, MD.schema(get_config("llama4-scout-17b-a16e")))
        (_, wq), = find(specs, "l0", "attn", "wq")
        assert wq[2] is None                               # heads replicated
        (_, wup), = find(specs, "l0", "ffn", "w_up")
        assert wup[1] == "model"                           # experts sharded

    def test_granite_mqa_kv_replicated(self):
        specs = RU.param_pspecs(SP, MD.schema(get_config("granite-20b")))
        (_, wk), = find(specs, "l0", "attn", "wk")
        assert wk[2] is None                               # kv=1 replicated
        (_, wq), = find(specs, "l0", "attn", "wq")
        assert wq[2] == "model"                            # 48 q heads shard

    def test_whisper_vocab_padded_shards(self):
        cfg = get_config("whisper-base")
        assert cfg.vocab_size == 51865 and cfg.padded_vocab == 51968
        assert cfg.padded_vocab % 16 == 0
        specs = RU.param_pspecs(SP, MD.schema(cfg))
        (_, emb), = find(specs, "=embed")
        assert emb[0] == "model"

    def test_multipod_fsdp_over_pod_and_data(self):
        specs = RU.param_pspecs(MP, MD.schema(get_config("mistral-large-123b")))
        (_, emb), = find(specs, "=embed")
        assert emb == P("model", ("pod", "data"))

    def test_infer_mode_drops_fsdp(self):
        specs = RU.param_pspecs(SP, MD.schema(get_config("llama3-8b")),
                                mode="infer")
        (_, emb), = find(specs, "=embed")
        assert emb == P("model", None)

    def test_param_bytes_estimate(self):
        sch = MD.schema(get_config("llama3-8b"))
        b_train = RU.param_bytes_per_chip(SP, sch, "train")
        b_infer = RU.param_bytes_per_chip(SP, sch, "infer")
        total = 2 * sum(int(np.prod(p.shape)) for p in
                        jax.tree_util.tree_leaves(
                            sch, is_leaf=lambda x: hasattr(x, "axes")))
        assert b_train < b_infer <= total
        assert b_infer < 2 * 2**30                         # ~1GB/chip @ 8B


class TestStateSpecs:
    def test_cache_seq_sharded_over_model(self):
        cfg = get_config("llama3-8b")
        state = jax.eval_shape(lambda: MD.init_decode_state(cfg, 128, 32768))
        specs = RU.decode_state_pspecs(cfg, SP, state)
        assert specs.cache_k == P(None, "data", "model", None, None)
        assert specs.freeze.c == P(None, "data", "model")

    def test_batch1_replicates(self):
        cfg = get_config("llama3-8b")
        state = jax.eval_shape(lambda: MD.init_decode_state(cfg, 1, 1024))
        specs = RU.decode_state_pspecs(cfg, SP, state)
        assert specs.cache_k[1] is None                    # B=1: no data shard

    def test_paged_pool_sharded(self):
        cfg = get_config("jamba-1.5-large-398b")
        state = jax.eval_shape(lambda: MD.init_paged_decode_state(cfg, 1, 1024))
        specs = RU.decode_state_pspecs(cfg, SP, state)
        assert specs.k == P(None, None, "model", None, None, None)
        assert specs.mamba["ssm"][2] == "model"            # d_inner sharded
