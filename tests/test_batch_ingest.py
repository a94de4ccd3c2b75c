"""Batch ingestion e2e: RecordReader SPI + readers + job runner + CLI.

Ref parity targets: RecordReader.java (SPI), CSVRecordReader/JSONRecordReader
(pinot-input-format), standalone SegmentGenerationJobRunner.java,
LaunchDataIngestionJobCommand, Quickstart.java — proven against the
reference's own baseballStats example configs
(/root/reference/pinot-tools/src/main/resources/examples/batch/baseballStats).
"""

import json
import os

import numpy as np
import pandas as pd
import pytest

from pinot_tpu.ingestion.batchjob import (
    SegmentGenerationJobRunner,
    SegmentGenerationJobSpec,
    run_ingestion_job,
)
from pinot_tpu.ingestion.readers import create_record_reader
from pinot_tpu.spi import Schema
from pinot_tpu.spi.table import TableConfig
from pinot_tpu.tools.cluster import EmbeddedCluster

REF_EXAMPLE = ("/root/reference/pinot-tools/src/main/resources/examples/"
               "batch/baseballStats")
REF_TEAMS_CSV = ("/root/reference/pinot-core/src/test/resources/data/"
                 "dimBaseballTeams.csv")

needs_reference = pytest.mark.skipif(
    not os.path.isdir("/root/reference"),
    reason="reads the Apache Pinot checkout at /root/reference, absent here")


def _synth_baseball_csv(path: str, n: int, seed: int) -> pd.DataFrame:
    """Synthesized rawdata for the reference's baseballStats schema (the
    checkout ships the schema/table-config/jobspec but not the CSV)."""
    schema = Schema.from_file(f"{REF_EXAMPLE}/baseballStats_schema.json")
    rng = np.random.default_rng(seed)
    cols = {}
    for fs in schema.field_specs:
        if fs.data_type.is_numeric:
            cols[fs.name] = rng.integers(0, 100, n)
        elif fs.name == "league":
            cols[fs.name] = np.array(["AL", "NL"])[rng.integers(0, 2, n)]
        elif fs.name == "teamID":
            cols[fs.name] = np.array(["BOS", "NYA", "SFN"])[
                rng.integers(0, 3, n)]
        else:
            cols[fs.name] = np.array([f"{fs.name}_{i % 17}"
                                      for i in range(n)])
    df = pd.DataFrame(cols)
    df.to_csv(path, index=False)
    return df


@needs_reference
def test_reference_jobspec_parses():
    spec = SegmentGenerationJobSpec.from_yaml(
        f"{REF_EXAMPLE}/ingestionJobSpec.yaml")
    assert spec.job_type == "SegmentCreationAndTarPush"
    assert spec.include_file_name_pattern == "glob:**/*.csv"
    assert spec.input_dir_uri.endswith("baseballStats/rawdata")
    assert spec.data_format == "csv"


@needs_reference
def test_baseball_quickstart_e2e(tmp_path):
    """The SURVEY.md minimum end-to-end slice: reference configs -> CSV ->
    job runner -> embedded cluster -> SQL answers match pandas."""
    raw = tmp_path / "rawdata"
    raw.mkdir()
    df1 = _synth_baseball_csv(str(raw / "part1.csv"), 700, seed=1)
    df2 = _synth_baseball_csv(str(raw / "part2.csv"), 500, seed=2)
    df = pd.concat([df1, df2], ignore_index=True)

    job = {
        "jobType": "SegmentCreationAndTarPush",
        "inputDirURI": "rawdata",
        "includeFileNamePattern": "glob:**/*.csv",
        "outputDirURI": "segments",
        "tableSpec": {
            "tableName": "baseballStats",
            "schemaURI": f"{REF_EXAMPLE}/baseballStats_schema.json",
            "tableConfigURI":
                f"{REF_EXAMPLE}/baseballStats_offline_table_config.json",
        },
        "recordReaderSpec": {"dataFormat": "csv"},
    }
    import yaml

    spec_file = tmp_path / "jobSpec.yaml"
    spec_file.write_text(yaml.safe_dump(job))

    schema = Schema.from_file(f"{REF_EXAMPLE}/baseballStats_schema.json")
    table_config = TableConfig.from_file(
        f"{REF_EXAMPLE}/baseballStats_offline_table_config.json")
    cluster = EmbeddedCluster(num_servers=2,
                              data_dir=str(tmp_path / "cluster"))
    try:
        cluster.create_table(table_config, schema)
        seg_dirs = run_ingestion_job(str(spec_file), cluster=cluster)
        assert len(seg_dirs) == 2
        assert cluster.wait_for_ev_converged("baseballStats_OFFLINE")

        rows = cluster.query_rows("SELECT count(*) FROM baseballStats")
        assert rows[0][0] == len(df)

        rows = cluster.query_rows(
            "SELECT league, sum(homeRuns), count(*) FROM baseballStats "
            "GROUP BY league ORDER BY league")
        exp = df.groupby("league").agg(hr=("homeRuns", "sum"),
                                       n=("homeRuns", "size")).sort_index()
        assert [r[0] for r in rows] == list(exp.index)
        assert [r[1] for r in rows] == pytest.approx(list(exp.hr))
        assert [r[2] for r in rows] == list(exp.n)

        rows = cluster.query_rows(
            "SELECT playerName, sum(runs) FROM baseballStats "
            "WHERE teamID = 'BOS' GROUP BY playerName "
            "ORDER BY sum(runs) DESC LIMIT 5")
        exp = (df[df.teamID == "BOS"].groupby("playerName").runs.sum()
               .sort_values(ascending=False).head(5))
        assert rows[0][1] == pytest.approx(exp.iloc[0])
    finally:
        cluster.shutdown()


@needs_reference
def test_real_reference_csv(tmp_path):
    """Ingest an actual CSV shipped in the reference checkout."""
    schema = Schema.from_dict({
        "schemaName": "dimBaseballTeams",
        "dimensionFieldSpecs": [
            {"name": "teamID", "dataType": "STRING"},
            {"name": "teamName", "dataType": "STRING"},
        ]})
    spec = SegmentGenerationJobSpec(
        input_dir_uri=os.path.dirname(REF_TEAMS_CSV),
        include_file_name_pattern="glob:dimBaseballTeams.csv",
        output_dir_uri=str(tmp_path / "segments"),
        table_name="dimBaseballTeams", data_format="csv")
    seg_dirs = SegmentGenerationJobRunner(spec, schema=schema).run()
    assert len(seg_dirs) == 1

    from pinot_tpu.engine import ServerQueryExecutor
    from pinot_tpu.query import compile_query
    from pinot_tpu.segment import load_segment

    seg = load_segment(seg_dirs[0])
    df = pd.read_csv(REF_TEAMS_CSV)
    assert seg.num_docs == len(df)
    ex = ServerQueryExecutor(use_device=False)
    rt, _ = ex.execute(compile_query(
        "SELECT count(*), distinctcount(teamID) FROM dimBaseballTeams"), [seg])
    assert rt.rows[0] == [len(df), df.teamID.nunique()]
    rt, _ = ex.execute(compile_query(
        "SELECT teamName FROM dimBaseballTeams WHERE teamID = 'BOS'"), [seg])
    assert rt.rows[0][0] == df[df.teamID == "BOS"].teamName.iloc[0]


def test_json_and_mv_csv_readers(tmp_path):
    jl = tmp_path / "rows.jsonl"
    jl.write_text('{"a": "x", "n": 1}\n{"a": "y", "n": 2}\n')
    rows = list(create_record_reader(str(jl)))
    assert rows == [{"a": "x", "n": 1}, {"a": "y", "n": 2}]

    arr = tmp_path / "rows.json"
    arr.write_text('[{"a": "x"}, {"a": "z", "tags": ["t1", "t2"]}]')
    rows = list(create_record_reader(str(arr)))
    assert rows[1]["tags"] == ["t1", "t2"]

    mv = tmp_path / "mv.csv"
    mv.write_text("name,tags\nbob,red;blue\neve,green\n")
    rows = list(create_record_reader(str(mv)))
    assert rows[0]["tags"] == ["red", "blue"]
    assert rows[1]["tags"] == "green"
    cols = create_record_reader(str(mv)).read_columnar()
    assert cols["tags"] == [["red", "blue"], "green"]


def test_parquet_reader(tmp_path):
    pq_file = tmp_path / "rows.parquet"
    df = pd.DataFrame({"city": ["sf", "nyc"], "v": [1, 2]})
    df.to_parquet(pq_file)
    reader = create_record_reader(str(pq_file))
    assert list(reader) == [{"city": "sf", "v": 1}, {"city": "nyc", "v": 2}]
    cols = reader.read_columnar()
    assert list(cols["city"]) == ["sf", "nyc"]


@needs_reference
def test_cli_quickstart(tmp_path, capsys):
    """Quickstart subcommand over a reference-layout example dir."""
    from pinot_tpu.tools.admin import main

    example = tmp_path / "example"
    raw = example / "rawdata"
    raw.mkdir(parents=True)
    df = _synth_baseball_csv(str(raw / "data.csv"), 300, seed=9)
    import shutil

    shutil.copy(f"{REF_EXAMPLE}/baseballStats_schema.json", example)
    shutil.copy(f"{REF_EXAMPLE}/baseballStats_offline_table_config.json",
                example)
    import yaml

    (example / "ingestionJobSpec.yaml").write_text(yaml.safe_dump({
        "jobType": "SegmentCreationAndTarPush",
        "inputDirURI": "rawdata",
        "includeFileNamePattern": "glob:**/*.csv",
        "outputDirURI": "segments",
        "tableSpec": {"tableName": "baseballStats"},
        "recordReaderSpec": {"dataFormat": "csv"},
    }))
    rc = main(["Quickstart", "-exampleDir", str(example),
               "-dataDir", str(tmp_path / "qs"),
               "-query", "SELECT count(*) FROM baseballStats"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    resp = json.loads(out[-1])
    assert resp["resultTable"]["rows"][0][0] == len(df)


@needs_reference
def test_cli_ingestion_job_command(tmp_path, capsys):
    """LaunchDataIngestionJob subcommand builds segments standalone."""
    from pinot_tpu.tools.admin import main

    raw = tmp_path / "rawdata"
    raw.mkdir()
    _synth_baseball_csv(str(raw / "d.csv"), 100, seed=4)
    import yaml

    spec_file = tmp_path / "job.yaml"
    spec_file.write_text(yaml.safe_dump({
        "jobType": "SegmentCreation",
        "inputDirURI": "rawdata",
        "includeFileNamePattern": "glob:**/*.csv",
        "outputDirURI": "out",
        "tableSpec": {
            "tableName": "baseballStats",
            "schemaURI": f"{REF_EXAMPLE}/baseballStats_schema.json"},
        "recordReaderSpec": {"dataFormat": "csv"},
    }))
    rc = main(["LaunchDataIngestionJob", "-jobSpecFile", str(spec_file)])
    assert rc == 0
    seg_dir = capsys.readouterr().out.strip().splitlines()[0]
    assert os.path.isdir(seg_dir)
    from pinot_tpu.segment import load_segment

    assert load_segment(seg_dir).num_docs == 100


def test_sv_string_with_semicolon_survives(tmp_path):
    """MV splitting is schema-aware: ';' inside an SV string is data, not a
    delimiter (regression: every cell used to split)."""
    csv_file = tmp_path / "d.csv"
    csv_file.write_text("name,tags\na;b,x;y\nplain,z\n")
    schema = Schema.from_dict({
        "schemaName": "t",
        "dimensionFieldSpecs": [
            {"name": "name", "dataType": "STRING"},
            {"name": "tags", "dataType": "STRING",
             "singleValueField": False},
        ]})
    spec = SegmentGenerationJobSpec(
        input_dir_uri=str(tmp_path), include_file_name_pattern="glob:*.csv",
        output_dir_uri=str(tmp_path / "out"), table_name="t",
        data_format="csv")
    seg_dirs = SegmentGenerationJobRunner(spec, schema=schema).run()
    from pinot_tpu.segment import load_segment

    seg = load_segment(seg_dirs[0])
    assert seg.get_value("name", 0) == "a;b"          # SV: intact
    assert list(seg.get_value("tags", 0)) == ["x", "y"]  # MV: split


def test_missing_csv_column_null_fills(tmp_path):
    """A schema column absent from the CSV header null-fills instead of
    crashing the columnar fast path."""
    csv_file = tmp_path / "d.csv"
    csv_file.write_text("a\nx\ny\n")
    schema = Schema.from_dict({
        "schemaName": "t",
        "dimensionFieldSpecs": [
            {"name": "a", "dataType": "STRING"},
            {"name": "missing", "dataType": "STRING"},
        ]})
    spec = SegmentGenerationJobSpec(
        input_dir_uri=str(tmp_path), include_file_name_pattern="glob:*.csv",
        output_dir_uri=str(tmp_path / "out"), table_name="t",
        data_format="csv")
    seg_dirs = SegmentGenerationJobRunner(spec, schema=schema).run()
    from pinot_tpu.segment import load_segment

    seg = load_segment(seg_dirs[0])
    assert seg.num_docs == 2
    assert seg.metadata.column("missing").has_nulls


def test_nulls_survive_transform_path(tmp_path):
    """JSON ingest (row path) must keep the null bitmap: defaults
    substituted by NullValueTransformer are not real values."""
    jl = tmp_path / "d.jsonl"
    jl.write_text('{"a": "x", "n": 5}\n{"a": "y"}\n')
    schema = Schema.from_dict({
        "schemaName": "t",
        "dimensionFieldSpecs": [{"name": "a", "dataType": "STRING"}],
        "metricFieldSpecs": [{"name": "n", "dataType": "LONG"}]})
    spec = SegmentGenerationJobSpec(
        input_dir_uri=str(tmp_path), include_file_name_pattern="glob:*.jsonl",
        output_dir_uri=str(tmp_path / "out"), table_name="t",
        data_format="jsonl")
    seg_dirs = SegmentGenerationJobRunner(spec, schema=schema).run()
    from pinot_tpu.engine import ServerQueryExecutor
    from pinot_tpu.query import compile_query
    from pinot_tpu.segment import load_segment

    seg = load_segment(seg_dirs[0])
    assert seg.metadata.column("n").has_nulls
    ex = ServerQueryExecutor(use_device=False)
    rt, _ = ex.execute(compile_query(
        "SELECT count(*) FROM t WHERE n IS NOT NULL"), [seg])
    assert rt.rows[0][0] == 1


def test_glob_star_does_not_cross_directories(tmp_path):
    """'glob:*.csv' is root-only (java glob semantics); '**/*.csv' recurses."""
    from pinot_tpu.ingestion.batchjob import _match_glob

    (tmp_path / "root.csv").write_text("a\n1\n")
    sub = tmp_path / "archive"
    sub.mkdir()
    (sub / "old.csv").write_text("a\n1\n")
    assert [os.path.basename(p)
            for p in _match_glob(str(tmp_path), "glob:*.csv")] == ["root.csv"]
    assert len(_match_glob(str(tmp_path), "glob:**/*.csv")) == 2
    assert [os.path.basename(p) for p in _match_glob(
        str(tmp_path), "glob:**/*.csv", exclude="glob:archive/*")] == \
        ["root.csv"]


def test_glob_braces_and_classes(tmp_path):
    from pinot_tpu.ingestion.batchjob import _match_glob

    for name in ("a.csv", "b.json", "c.txt", "d1.csv"):
        (tmp_path / name).write_text("x\n1\n")
    got = [os.path.basename(p)
           for p in _match_glob(str(tmp_path), "glob:*.{csv,json}")]
    assert got == ["a.csv", "b.json", "d1.csv"]
    got = [os.path.basename(p)
           for p in _match_glob(str(tmp_path), "glob:[ab].*")]
    assert got == ["a.csv", "b.json"]


def test_columnar_path_sanitizes(tmp_path):
    """NUL stripping + maxLength truncation apply on the columnar fast
    path too (regression: only the row path sanitized)."""
    csv_file = tmp_path / "d.csv"
    long = "x" * 600
    csv_file.write_text(f"a\nhas\x00nul\n{long}\n")
    schema = Schema.from_dict({
        "schemaName": "t",
        "dimensionFieldSpecs": [
            {"name": "a", "dataType": "STRING", "maxLength": 512}]})
    spec = SegmentGenerationJobSpec(
        input_dir_uri=str(tmp_path), include_file_name_pattern="glob:*.csv",
        output_dir_uri=str(tmp_path / "out"), table_name="t",
        data_format="csv")
    seg_dirs = SegmentGenerationJobRunner(spec, schema=schema).run()
    from pinot_tpu.segment import load_segment

    seg = load_segment(seg_dirs[0])
    assert seg.get_value("a", 0) == "hasnul"
    assert len(seg.get_value("a", 1)) == 512


def test_parquet_missing_column_null_fills(tmp_path):
    pq_file = tmp_path / "d.parquet"
    pd.DataFrame({"a": ["x", "y"]}).to_parquet(pq_file)
    reader = create_record_reader(str(pq_file),
                                  fields_to_read=["a", "missing"])
    assert list(reader) == [{"a": "x", "missing": None},
                            {"a": "y", "missing": None}]
    cols = reader.read_columnar()
    assert cols["missing"] == [None, None]


def test_empty_csv_raises_meaningfully(tmp_path):
    (tmp_path / "empty.csv").write_text("")
    with pytest.raises(ValueError, match="empty CSV"):
        create_record_reader(str(tmp_path / "empty.csv"))


def test_glob_braces_with_wildcards(tmp_path):
    from pinot_tpu.ingestion.batchjob import _match_glob

    for name in ("a.csv", "b.json", "c.txt"):
        (tmp_path / name).write_text("x\n1\n")
    got = [os.path.basename(p)
           for p in _match_glob(str(tmp_path), "glob:{*.csv,*.json}")]
    assert got == ["a.csv", "b.json"]


def test_job_parallelism_builds_all_segments(tmp_path):
    """segmentCreationJobParallelism > 1: per-file builds run in a process
    pool; every matched file still becomes exactly one segment (ref: the
    runner's ExecutorService fan-out)."""
    import numpy as np

    from pinot_tpu.ingestion.batchjob import (
        SegmentGenerationJobRunner,
        SegmentGenerationJobSpec,
    )
    from pinot_tpu.segment import load_segment
    from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema

    inp = tmp_path / "in"
    inp.mkdir()
    rng = np.random.default_rng(2)
    for i in range(4):
        lines = ["k,v"] + [f"k{j % 3},{int(rng.integers(0, 9))}"
                           for j in range(200)]
        (inp / f"part{i}.csv").write_text("\n".join(lines))
    schema = Schema("pj", [
        FieldSpec("k", DataType.STRING),
        FieldSpec("v", DataType.LONG, FieldType.METRIC)])
    spec = SegmentGenerationJobSpec(
        input_dir_uri=str(inp), include_file_name_pattern="glob:**/*.csv",
        output_dir_uri=str(tmp_path / "out"), table_name="pj",
        data_format="csv", parallelism=4)
    dirs = SegmentGenerationJobRunner(spec, schema=schema).run()
    assert len(dirs) == 4
    total = sum(load_segment(d).num_docs for d in dirs)
    assert total == 800
