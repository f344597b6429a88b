"""Benchmark model pipelines run end-to-end, differential vs the CPU
oracle (SURVEY §4 tier 3)."""

import pytest

from spark_rapids_tpu.plan import TpuSession
from spark_rapids_tpu.testing import assert_tpu_cpu_equal_df


@pytest.fixture(scope="module")
def session():
    return TpuSession()


@pytest.fixture(scope="module")
def tpch(session, tmp_path_factory):
    from spark_rapids_tpu.models import tpch_tables
    d = tmp_path_factory.mktemp("tpch")
    return tpch_tables(session, str(d), scale_rows=20_000,
                       chunk_rows=8_192)


def test_q6(session, tpch):
    from spark_rapids_tpu.models import q6
    df = q6(tpch["lineitem"])
    out = df.collect()
    assert len(out) == 1
    assert out[0]["revenue"] is None or out[0]["revenue"] > 0
    assert_tpu_cpu_equal_df(df)


def test_q1(session, tpch):
    from spark_rapids_tpu.models import q1
    df = q1(tpch["lineitem"])
    out = df.collect()
    # 3 returnflags x 2 linestatuses
    assert 1 <= len(out) <= 6
    assert_tpu_cpu_equal_df(df, approx_float=1e-5)


def test_q3(session, tpch):
    from spark_rapids_tpu.models import q3
    df = q3(tpch["customer"], tpch["orders"], tpch["lineitem"])
    out = df.collect()
    assert len(out) <= 10
    revs = [r["revenue"] for r in out]
    assert revs == sorted(revs, reverse=True)
    assert_tpu_cpu_equal_df(df, approx_float=1e-5, ignore_order=False)


def test_mortgage_etl(session, tmp_path):
    from spark_rapids_tpu.models import mortgage_etl, mortgage_tables
    t = mortgage_tables(session, str(tmp_path / "m"), n_loans=2_000)
    feats = mortgage_etl(t["acquisitions"], t["performance"])
    out = feats.limit(50).collect()
    assert out and set(out[0]) >= {"loan_id", "n_reports", "ever_90",
                                   "credit_score", "state"}
    assert_tpu_cpu_equal_df(mortgage_etl(t["acquisitions"],
                                         t["performance"]),
                            approx_float=1e-5)
    # ML hand-off
    arrs = feats.to_device_arrays()
    assert arrs.num_rows > 0 and "ever_90" in arrs


class TestTpcds:
    """TPC-DS-shaped breadth (models/tpcds.py) — differential vs the
    CPU oracle (operator coverage)."""

    @pytest.fixture(scope="class")
    def tables(self, tmp_path_factory):
        from spark_rapids_tpu.models import tpcds
        from spark_rapids_tpu.plan import TpuSession
        session = TpuSession()
        d = str(tmp_path_factory.mktemp("tpcds"))
        return tpcds.tpcds_tables(session, d, scale_rows=30_000)

    def test_q3(self, tables):
        from spark_rapids_tpu.models import tpcds
        assert_tpu_cpu_equal_df(tpcds.q3(
            tables["store_sales"], tables["date_dim"], tables["item"]))

    def test_q42(self, tables):
        from spark_rapids_tpu.models import tpcds
        assert_tpu_cpu_equal_df(tpcds.q42(
            tables["store_sales"], tables["date_dim"], tables["item"]))

    def test_q55(self, tables):
        from spark_rapids_tpu.models import tpcds
        assert_tpu_cpu_equal_df(tpcds.q55(
            tables["store_sales"], tables["date_dim"], tables["item"]))

    def test_q68r(self, tables):
        from spark_rapids_tpu.models import tpcds
        assert_tpu_cpu_equal_df(tpcds.q68r(
            tables["store_sales"], tables["date_dim"], tables["item"]))
