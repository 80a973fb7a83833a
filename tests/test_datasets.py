"""repro.datasets: registry round-trips, seeded determinism, plan cache."""
import numpy as np
import pytest

from repro import datasets
from repro.datasets import plans, registry
from repro.graph import synthetic
from repro.graph.formats import Graph


def _graph_equal(a, b) -> bool:
    if a.n_nodes != b.n_nodes or a.n_classes != b.n_classes:
        return False
    for f in ("edge_index", "x", "y", "train_mask", "val_mask", "test_mask",
              "pos", "edge_attr"):
        va, vb = getattr(a, f), getattr(b, f)
        if (va is None) != (vb is None):
            return False
        if va is not None and (va.shape != vb.shape or not (va == vb).all()):
            return False
    return True


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_names_cover_the_paper_graphs():
    names = datasets.names()
    for required in ("reddit_like", "yelp_like", "products_like",
                     "amazon_like", "mesh_like", "molecule_like"):
        assert required in names


@pytest.mark.parametrize("name", registry.names())
def test_every_tier_loads_and_is_deterministic_smoke(name):
    spec = registry.get(name)
    assert set(spec.tiers) == set(registry.TIERS)
    g1 = spec.load("smoke", seed=7)
    g2 = spec.load("smoke", seed=7)
    assert isinstance(g1, Graph)
    assert _graph_equal(g1, g2)
    # a different seed produces a different graph
    g3 = spec.load("smoke", seed=8)
    assert not _graph_equal(g1, g3)
    # calibration sanity: requested widths/classes survive generation
    kw = spec.tiers["smoke"]
    assert g1.x.shape[1] == kw["d_feat"]
    if "n_classes" in kw:
        assert g1.n_classes == kw["n_classes"]


@pytest.mark.slow
@pytest.mark.parametrize("tier", ["small", "paper"])
@pytest.mark.parametrize("name", registry.names())
def test_big_tiers_round_trip_deterministically(name, tier):
    spec = registry.get(name)
    g1 = spec.load(tier, seed=0)
    g2 = spec.load(tier, seed=0)
    assert _graph_equal(g1, g2)
    # tiers are ordered by size
    smaller = spec.load("smoke" if tier == "small" else "small", seed=0)
    assert g1.n_nodes > smaller.n_nodes


def test_parse_refs_and_errors():
    assert registry.parse("reddit_like@paper") == ("reddit_like", "paper")
    assert registry.parse("mesh_like") == ("mesh_like", "smoke")
    with pytest.raises(KeyError, match="tier"):
        registry.parse("reddit_like@huge")
    with pytest.raises(KeyError, match="unknown workload"):
        datasets.load("no_such_graph@smoke")
    with pytest.raises(KeyError, match="no tier"):
        registry.get("mesh_like").load("gigantic")


def test_load_ref_matches_explicit_tier():
    a = datasets.load("products_like@smoke", seed=1)
    b = datasets.load("products_like", tier="smoke", seed=1)
    assert _graph_equal(a, b)


def test_powerlaw_community_is_heavy_tailed_and_homophilous():
    g = synthetic.powerlaw_community(n_nodes=1500, n_classes=8, d_feat=16,
                                     avg_degree=16, p_in=0.8, seed=0)
    deg = g.degrees("in")
    assert deg.max() > 8 * deg.mean()          # hubs exist
    src, dst = g.edge_index
    same = (g.y[src] == g.y[dst]).mean()
    assert same > 0.5                          # homophily >> 1/8 random rate


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_miss_then_hit_round_trips(tmp_path):
    pg1, hit1 = datasets.load_partitioned("yelp_like@smoke", 4,
                                          cache_dir=tmp_path)
    assert not hit1
    pg2, hit2 = datasets.load_partitioned("yelp_like@smoke", 4,
                                          cache_dir=tmp_path)
    assert hit2
    assert pg2.plan.layout == pg1.plan.layout == "compact"
    assert pg2.plan.alignment == pg1.plan.alignment
    for f in ("send_idx", "send_mask", "recv_mask", "bucket_sizes",
              "pair_counts"):
        np.testing.assert_array_equal(getattr(pg1.plan, f),
                                      getattr(pg2.plan, f))
    for f in ("part_of", "global_ids", "node_mask", "x", "y", "train_mask",
              "val_mask", "test_mask", "edges", "edge_mask", "edge_weight"):
        np.testing.assert_array_equal(np.asarray(getattr(pg1, f)),
                                      np.asarray(getattr(pg2, f)))
    assert pg1.edges.dtype == pg2.edges.dtype
    assert pg2.plan.send_idx.dtype == pg1.plan.send_idx.dtype


def test_plan_cache_never_loads_an_entry_of_another_version(tmp_path,
                                                           monkeypatch):
    """An entry written before the edges were sorted by destination (cache
    version 1) is never referenced: the version is part of the key."""
    g = registry.load("yelp_like@smoke")
    monkeypatch.setattr(plans, "CACHE_VERSION", 1)
    assert not plans.cached_partition(g, 4, cache_dir=tmp_path)[1]
    monkeypatch.undo()
    pg, hit = plans.cached_partition(g, 4, cache_dir=tmp_path)
    assert not hit and len(list(tmp_path.glob("*.npz"))) == 2
    for p in range(4):
        k = int(pg.edge_mask[p].sum())
        assert (np.diff(pg.edges[p, :k, 1]) >= 0).all()


def test_cached_partition_trains_identically(tmp_path):
    """A cache-loaded PartitionedGraph is a drop-in for a fresh one."""
    from repro.core.sylvie import SylvieConfig
    from repro.models.gnn.models import GCN
    from repro.train.trainer import GNNTrainer

    losses = []
    for _ in range(2):                          # miss, then hit
        pg, _ = datasets.load_partitioned("products_like@smoke", 4,
                                          cache_dir=tmp_path)
        model = GCN(pg.x.shape[-1], 16, pg.n_classes, n_layers=2)
        tr = GNNTrainer(model, pg, SylvieConfig(mode="sync", bits=1))
        tr.fit(2)
        losses.append([m.loss for m in tr.history])
    assert losses[0] == losses[1]


def test_plan_cache_key_invalidation(tmp_path):
    g = datasets.load("yelp_like@smoke")
    base = plans.plan_key(g, 4)
    assert base == plans.plan_key(g, 4)                       # stable
    assert plans.plan_key(g, 4, alignment=16) != base         # alignment
    assert plans.plan_key(g, 8) != base                       # n_parts
    assert plans.plan_key(g, 4, layout="dense") != base       # layout
    assert plans.plan_key(g, 4, method="random") != base      # method
    g2 = datasets.load("yelp_like@smoke", seed=1)
    assert plans.plan_key(g2, 4) != base                      # graph content


def test_plan_cache_alignment_change_is_a_miss(tmp_path):
    _, hit = datasets.load_partitioned("yelp_like@smoke", 4,
                                       cache_dir=tmp_path)
    assert not hit
    pg16, hit = datasets.load_partitioned("yelp_like@smoke", 4, alignment=16,
                                          cache_dir=tmp_path)
    assert not hit                              # different key -> repartition
    assert pg16.plan.alignment == 16
    assert all(b % 16 == 0 for b in pg16.plan.bucket_sizes)
    # both entries coexist; the original still hits
    _, hit = datasets.load_partitioned("yelp_like@smoke", 4,
                                       cache_dir=tmp_path)
    assert hit


def test_plan_cache_corrupt_entry_is_rewritten(tmp_path):
    datasets.load_partitioned("mesh_like@smoke", 2, cache_dir=tmp_path)
    (entry,) = tmp_path.glob("*.npz")
    entry.write_bytes(b"not an npz")
    pg, hit = datasets.load_partitioned("mesh_like@smoke", 2,
                                        cache_dir=tmp_path)
    assert not hit                              # treated as a miss
    pg2, hit = datasets.load_partitioned("mesh_like@smoke", 2,
                                         cache_dir=tmp_path)
    assert hit                                  # and the entry was repaired
    np.testing.assert_array_equal(pg.edges, pg2.edges)


def test_dense_layout_round_trips_through_cache(tmp_path):
    pg, _ = datasets.load_partitioned("yelp_like@smoke", 4, layout="dense",
                                      cache_dir=tmp_path)
    pg2, hit = datasets.load_partitioned("yelp_like@smoke", 4, layout="dense",
                                         cache_dir=tmp_path)
    assert hit and pg2.plan.layout == "dense"
    assert pg2.plan.bucket_sizes is None and pg2.plan.pair_counts is not None
    np.testing.assert_array_equal(pg.plan.send_idx, pg2.plan.send_idx)
