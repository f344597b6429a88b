import hashlib
import os

import pandas as pd

from benchmarks.harness import datagen
from benchmarks.tests.conftest import load_config


def digest(directory: str) -> str:
    h = hashlib.sha256()
    for name in sorted(os.listdir(directory)):
        with open(os.path.join(directory, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def test_same_seed_same_bytes_other_seed_other_data(tmp_path):
    config = load_config("nds_tiny")
    big = 2**31 + 12345  # the driver's seeds are larger than 32 signed bits hold
    a, _ = datagen.generate(config, ["store_sales", "item"], big, str(tmp_path / "a"))
    b, _ = datagen.generate(config, ["store_sales", "item"], big, str(tmp_path / "b"))
    c, _ = datagen.generate(config, ["store_sales", "item"], big + 1, str(tmp_path / "c"))
    for table in a:
        assert digest(a[table]) == digest(b[table])
        assert digest(a[table]) != digest(c[table])
    # another seed is other values in a table of the same size: the same work
    fa, fc = pd.read_parquet(a["store_sales"]), pd.read_parquet(c["store_sales"])
    assert fa.shape == fc.shape == (config["tables"]["store_sales"]["rows"], 22)
    assert not fa["ss_ext_sales_price"].equals(fc["ss_ext_sales_price"])


def test_columns_follow_their_specs(tmp_path):
    config = load_config("tpch_tiny")
    paths, written = datagen.generate(config, ["lineitem"], 5, str(tmp_path))
    frame = pd.read_parquet(paths["lineitem"])
    assert written > 0 and len(frame) == config["tables"]["lineitem"]["rows"]
    assert set(frame["l_returnflag"]) == {"A", "N", "R"}
    assert frame["l_discount"].between(0.0, 0.1).all() and frame["l_quantity"].between(1, 50).all()
    items = datagen.generate(load_config("nds_tiny"), ["item"], 5, str(tmp_path / "nds"))[0]
    item = pd.read_parquet(items["item"])
    assert item.set_index("i_item_sk")["i_item_id"][3] == "ITEM00000000003" and item["i_item_sk"].min() == 1
    assert item["i_brand"].str.fullmatch(r"brand#\d+").all()
    assert item["i_current_price"].isna().mean() < 0.1


def test_logical_bytes_are_rows_times_referenced_widths():
    tpch, nds = load_config("tpch_sf1"), load_config("nds_sf1")
    # q6: DATE 4 + three DOUBLE 8 = 28 B a row over the 6,001,215 rows of scale factor 1
    assert datagen.logical_bytes(tpch, "q6") == 28 * 6_001_215 == 168_034_020
    rows = nds["store_sales_rows"]
    assert rows == nds["tables"]["store_sales"]["rows"] == 2_880_404  # TPC-DS scale factor 1
    assert nds["tables"]["item"]["rows"] == nds["item_rows"] == 18_000
    assert nds["tables"]["date_dim"]["rows"] == nds["date_dim_rows"] == 73_049
    for qid in ("q3", "q42", "q52"):
        # two INT64 keys and one DOUBLE of store_sales: 24 B a row; date_dim and item add their few MB
        dims = datagen.logical_bytes(nds, qid) - 24 * rows
        assert 24 * 73_049 + 16 * 18_000 < dims < 24 * 73_049 + 40 * 18_000
        assert datagen.fact_rows(nds, qid) == rows
    assert datagen.fact_rows(tpch, "q6") == 6_001_215


def test_fixed_columns_are_the_same_rows_in_another_order_for_another_seed(tmp_path):
    """What decides the sizes of a query's filters, joins and groups does not follow the seed; what it adds up does."""
    config = load_config("nds_tiny")
    assert config["tables"]["item"]["seeded_columns"] == []
    seeded = config["tables"]["store_sales"]["seeded_columns"]
    assert "ss_ext_sales_price" in seeded and "ss_item_sk" not in seeded
    a, _ = datagen.generate(config, ["store_sales", "item"], 1, str(tmp_path / "a"))
    b, _ = datagen.generate(config, ["store_sales", "item"], 2, str(tmp_path / "b"))
    for table in ("store_sales", "item"):
        fa, fb = pd.read_parquet(a[table]), pd.read_parquet(b[table])
        fixed = [c for c in fa.columns if c not in config["tables"][table]["seeded_columns"]]
        assert not fa[fixed].equals(fb[fixed])  # another order
        pd.testing.assert_frame_equal(fa[fixed].sort_values(fixed).reset_index(drop=True),
                                      fb[fixed].sort_values(fixed).reset_index(drop=True))  # the same rows
    assert not fa["i_item_sk"].equals(fb["i_item_sk"])
    sa, sb = pd.read_parquet(a["store_sales"]), pd.read_parquet(b["store_sales"])
    assert sa["ss_ext_sales_price"].sum() != sb["ss_ext_sales_price"].sum()


def test_tpch_keys_are_dbgens(tmp_path):
    """Sparse order keys, one to seven lines an order in order, customers that skip every third key."""
    config = load_config("tpch_tiny")
    paths, _ = datagen.generate(config, ["lineitem", "orders", "customer"], 9, str(tmp_path))
    lines, orders, customers = (pd.read_parquet(paths[t]) for t in ("lineitem", "orders", "customer"))
    assert len(lines) == config["tables"]["lineitem"]["rows"]
    assert orders["o_orderkey"].tolist()[:10] == [1, 2, 3, 4, 5, 6, 7, 8, 33, 34]
    per_order = lines["l_orderkey"].value_counts()
    assert per_order.between(1, 7).all() and set(per_order.index) == set(orders["o_orderkey"])
    assert lines["l_orderkey"].is_monotonic_increasing
    assert (orders["o_custkey"] % 3 != 0).all() and orders["o_custkey"].isin(customers["c_custkey"]).all()
    assert orders["o_custkey"].nunique() > 0.6 * len(customers) * 2 / 3  # uniform, not Zipf


def test_date_dim_is_a_calendar_and_names_follow_ids(tmp_path):
    config = load_config("nds_sf1")
    paths, _ = datagen.generate(config, ["date_dim", "item"], 3, str(tmp_path))
    dates = pd.read_parquet(paths["date_dim"]).set_index("d_date_sk").sort_index()
    assert len(dates) == 73_049 and dates.index[0] == 2415022
    first_sale = dates.loc[config["tables"]["store_sales"]["columns"][0]["lo"]]
    assert (str(first_sale["d_date"]), first_sale["d_year"], first_sale["d_moy"], first_sale["d_dom"]) == \
        ("1998-01-02", 1998, 1, 2)
    leap = dates[(dates["d_year"] == 2000) & (dates["d_moy"] == 2)]
    assert len(leap) == 29 and leap["d_qoy"].eq(1).all()
    item = pd.read_parquet(paths["item"])
    assert (item["i_brand"] == "brand#" + item["i_brand_id"].astype(str)).all()
    assert item.groupby("i_category_id")["i_category"].nunique().eq(1).all()
    assert item["i_manufact_id"].max() > 900 and item["i_manager_id"].max() == 100
