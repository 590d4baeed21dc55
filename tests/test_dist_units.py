"""Unit tests for dist helpers: the dispatch layer's leaf_chunks
padding/reshape invariants and logical.lc inside vs outside a
logical_rules context."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import elastic, logical
from repro.kernels import ops as kops

jax.config.update("jax_platform_name", "cpu")


def _auto_mesh():
    """A (1, 1) ("data", "model") mesh with Auto axes, built the way the
    program builds its own (``elastic.make_mesh``)."""
    return elastic.make_mesh(devices=jax.devices()[:1])


class TestLeafChunks:
    def test_even_split_no_padding(self):
        a = jnp.arange(12.0).reshape(12, 1)
        (c,), ml = kops.leaf_chunks((a,), 4)
        assert ml == 12
        assert c.shape == (3, 4, 1)
        np.testing.assert_array_equal(np.asarray(c.reshape(12, 1)),
                                      np.asarray(a))

    def test_odd_leaf_count_pads_with_zeros(self):
        a = jnp.arange(1.0, 8.0)          # 7 leaves, chunk 3 -> pad 2
        (c,), ml = kops.leaf_chunks((a,), 3)
        assert ml == 7
        assert c.shape == (3, 3)
        flat = np.asarray(c.reshape(-1))
        np.testing.assert_array_equal(flat[:7], np.arange(1.0, 8.0))
        np.testing.assert_array_equal(flat[7:], 0.0)

    def test_chunk_larger_than_ml(self):
        a = jnp.ones((5, 2, 3))
        (c,), ml = kops.leaf_chunks((a,), 8)
        assert ml == 5
        assert c.shape == (1, 8, 2, 3)
        # trailing dims are never padded
        np.testing.assert_array_equal(np.asarray(c[0, :5]), np.asarray(a))
        np.testing.assert_array_equal(np.asarray(c[0, 5:]), 0.0)

    def test_multiple_arrays_share_layout(self):
        arrays = (jnp.arange(10.0), jnp.ones((10, 4), bool))
        out, ml = kops.leaf_chunks(arrays, 4)
        assert ml == 10
        assert out[0].shape == (3, 4) and out[1].shape == (3, 4, 4)
        # un-chunk + strip padding round-trips every array
        for orig, chunked in zip(arrays, out):
            back = chunked.reshape(-1, *chunked.shape[2:])[:ml]
            np.testing.assert_array_equal(np.asarray(back), np.asarray(orig))

    def test_roundtrip_matches_chunked_map(self):
        # the dispatch usage pattern: lax.map over chunks == direct compute
        a = jnp.arange(7.0)
        chunks, ml = kops.leaf_chunks((a,), 2)
        y = jax.lax.map(lambda s: s[0] * 2.0, chunks)
        np.testing.assert_array_equal(np.asarray(y.reshape(-1)[:ml]),
                                      np.asarray(a) * 2.0)

class TestLogicalConstraint:
    def test_lc_outside_context_is_identity(self):
        x = jnp.ones((4, 6))
        assert logical.lc(x, "batch", "ff") is x

    def test_lc_inside_context_constrains(self):
        mesh = _auto_mesh()
        x = jnp.arange(24.0).reshape(4, 6)
        with logical.logical_rules(mesh, logical.RULES_V0):
            y = jax.jit(lambda v: logical.lc(v, "batch", "ff") * 1.0)(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))

    def test_lc_rank_mismatch_raises(self):
        mesh = _auto_mesh()
        with logical.logical_rules(mesh, logical.RULES_V0):
            with pytest.raises(ValueError, match="rank"):
                logical.lc(jnp.ones((2, 2)), "batch")

    def test_priority_resolves_mesh_axis_conflicts(self):
        # seq_shard and heads both map to "model"; seq_shard has priority,
        # heads replicates (sequence-parallel v0 attention).
        mesh = _auto_mesh()
        with logical.logical_rules(mesh, logical.RULES_V0):
            assert logical.spec(("batch", "seq_shard", "heads", None)) == \
                P(("data",), "model", None, None)
            assert logical.spec(("batch", "heads", None, "seq_shard")) == \
                P(("data",), None, None, "model")

    def test_axis_size_and_rules_with(self):
        mesh = _auto_mesh()
        assert logical.axis_size("batch") == 1  # no context
        rules = logical.rules_with(points="model", ff=None)
        with logical.logical_rules(mesh, rules):
            assert logical.spec(("points",)) == P("model")
            assert logical.spec(("ff",)) == P(None)
            assert logical.axis_size("batch") == 1  # (1,1) mesh

    def test_nested_contexts_restore(self):
        mesh = _auto_mesh()
        with logical.logical_rules(mesh, logical.RULES_V0):
            with logical.logical_rules(mesh, logical.rules_with(ff=None)):
                assert logical.spec(("ff",)) == P(None)
            assert logical.spec(("ff",)) == P("model")
        assert logical.current() is None
