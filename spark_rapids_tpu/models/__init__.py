"""Benchmark workloads: TPC-H-shaped queries + mortgage ETL.

Rebuild of the reference's integration benchmark apps (SURVEY §4 tier
3: mortgage/MortgageSpark.scala, scaletest/). Each function takes a
session and table DataFrames and returns a DataFrame; datagen.py
supplies the deterministic inputs.
"""

from .tpch import q1, q3, q6, tpch_tables
from .mortgage import mortgage_etl, mortgage_tables

__all__ = ["q1", "q3", "q6", "tpch_tables", "mortgage_etl",
           "mortgage_tables"]
