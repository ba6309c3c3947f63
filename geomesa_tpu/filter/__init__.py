"""Filter model: predicate AST, ECQL parsing, and index-value extraction.

The analogue of the reference's `geomesa-filter` module (SURVEY.md section
2.3): decompose CQL into the geometries/intervals/bounds the indexes can
accelerate, and evaluate the full predicate tree columnar-batch-wise for
exact refinement.
"""

from geomesa_tpu.filter.predicates import (
    And,
    BBox,
    Between,
    Cmp,
    Contains,
    During,
    DWithin,
    EXCLUDE,
    Exclude,
    Filter,
    IdFilter,
    In,
    INCLUDE,
    Include,
    Intersects,
    IsNull,
    Like,
    Not,
    Or,
    PointColumn,
    Slices,
    Within,
)
from geomesa_tpu.filter.ecql import parse, parse_dt_millis
from geomesa_tpu.filter.extract import (
    Bounds,
    FilterValues,
    Interval,
    extract_attribute_bounds,
    extract_geometries,
    extract_ids,
    extract_intervals,
    geometry_bounds,
)

__all__ = [
    "Filter", "Include", "Exclude", "INCLUDE", "EXCLUDE",
    "BBox", "Intersects", "Contains", "Within", "DWithin",
    "During", "Cmp", "Between", "In", "Like", "IsNull", "IdFilter",
    "And", "Or", "Not", "Slices", "PointColumn",
    "parse", "parse_dt_millis",
    "FilterValues", "Interval", "Bounds",
    "extract_geometries", "extract_intervals", "extract_ids",
    "extract_attribute_bounds", "geometry_bounds",
]
