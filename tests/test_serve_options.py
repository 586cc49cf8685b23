"""The seam serving/options.py is: one table, read by every surface that
names a serving option. These pin that the surfaces agree."""

import inspect
import os

import pytest

from datatunerx_tpu import cli
from datatunerx_tpu.gateway import server as gateway_server
from datatunerx_tpu.serving import options, server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# BatchedEngine keywords that are not deployment options: what the server
# process passes itself, and what only tests and the parity oracle select
PROGRAMMATIC_ONLY = ("registry", "dtype", "tracing", "paged_kernel",
                     "sampling_epilogue")


def _parse_server(argv):
    return server.build_parser().parse_args(argv)


def _parse_gateway(argv):
    return gateway_server.build_parser().parse_args(argv)


def _parse_dtx_serve(argv):
    return cli.build_parser().parse_args(["serve"] + argv)


PARSERS = [_parse_server, _parse_gateway, _parse_dtx_serve]


def _other_value(o):
    if o.choices:
        return next(c for c in o.choices if c != o.default)
    return {"adapters": "a=/ckpt/a", "spec_tree": "4x3"}.get(
        o.name, {int: 7, float: 7.5, str: "x"}[o.type])


@pytest.mark.parametrize("o", options.OPTIONS, ids=lambda o: o.name)
def test_three_parsers_share_each_default(o):
    if o.name == "model_path":  # required by the two that serve directly
        assert _parse_gateway([]).model_path == o.default
        return
    got = [getattr(parse(["--model_path", "m"]), o.name) for parse in PARSERS]
    assert got == [o.default] * 3


@pytest.mark.parametrize("o", options.OPTIONS, ids=lambda o: o.name)
def test_argv_round_trips_through_the_server_parser(o):
    """What `dtx serve` parsed reaches the server it starts, unchanged."""
    value = _other_value(o)
    base = [] if o.name == "model_path" else ["--model_path", "m"]
    parsed = _parse_dtx_serve(base + [f"--{o.name}", str(value)])
    argv = options.argv(parsed)
    assert argv == base + [f"--{o.name}", str(value)]
    assert getattr(_parse_server(argv), o.name) == value
    # a spec dict (the operator's form) renders the same flags
    assert options.argv({**({"model_path": "m"} if base else {}),
                         o.name: value}) == argv


def test_rows_are_the_batched_engines_keywords():
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    params = set(inspect.signature(BatchedEngine.__init__).parameters)
    params.discard("self")
    keywords = {o.engine or o.name for o in options.OPTIONS
                if o.engine is not None}
    assert keywords <= params, keywords - params
    assert params - keywords == set(PROGRAMMATIC_ONLY)
    args = _parse_server(["--model_path", "m", "--adapters", "a=/x",
                          "--adapter_targets", "q_proj, o_proj"])
    kw = options.engine_kwargs(args)
    assert set(kw) == keywords
    assert kw["adapters"] == {"a": "/x"}
    assert kw["adapter_targets"] == ["q_proj", "o_proj"]
    assert kw["kv_quant"] is None and kw["trace_log_path"] is None


@pytest.mark.parametrize("flag", ["--paged_kernel", "--sampling_epilogue"])
@pytest.mark.parametrize("parse", PARSERS, ids=["server", "gateway", "dtx"])
def test_path_selectors_are_not_flags(parse, flag, capsys):
    with pytest.raises(SystemExit):
        parse(["--model_path", "m", flag, "off"])
    assert "unrecognized arguments" in capsys.readouterr().err


def test_crd_has_the_tables_keys_and_no_selector():
    from datatunerx_tpu.operator.api import FinetuneJob
    from datatunerx_tpu.operator.crdgen import crd_for

    serve = (crd_for(FinetuneJob)["spec"]["versions"][0]["schema"]
             ["openAPIV3Schema"]["properties"]["spec"]["properties"]
             ["serveConfig"]["properties"])
    assert "samplingEpilogue" not in serve and "pagedKernel" not in serve
    for o in options.OPTIONS:
        if o.crd:
            assert serve[o.crd] == options.crd_properties()[o.crd]


def test_serve_config_checks_come_from_the_table():
    from datatunerx_tpu.operator.webhooks import (
        AdmissionError,
        _validate_serve_config,
    )

    _validate_serve_config({"specDraft": "take:1", "specTree": "4x3",
                            "specMode": "on", "kvOvercommit": ""})
    for bad, needle in (({"specTree": "4x3"}, "requires specDraft"),
                        ({"specDraft": "d", "specTree": "4"}, "WxD"),
                        ({"specDraft": "d", "specTree": "65x2"},
                         "out of range"),
                        ({"quantization": "fp4"}, "serveConfig.quantization"),
                        ({"slots": "many"}, "serveConfig.slots"),
                        ({"samplingEpilogue": "off", "specK": 0},
                         "serveConfig.specK")):
        with pytest.raises(AdmissionError, match=needle):
            _validate_serve_config(bad)
    assert options.from_serve_config(
        {"adapterPool": 16, "kvOvercommit": "on", "specMode": "",
         "replicas": 3}) == {"adapter_pool": 16, "kv_overcommit": "on"}


def test_single_slot_engine_refuses_what_only_the_batched_one_honours():
    args = _parse_server(["--model_path", "m", "--slots", "1",
                          "--kv_overcommit", "on", "--spec_k", "2"])
    assert options.requires_batched(args) == ["--kv_overcommit"]
    assert options.requires_batched(_parse_server(["--model_path", "m"])) == []


def test_readme_flag_reference_is_the_table():
    rows = ["| flag | default | `serveConfig` | |", "|---|---|---|---|"]
    for o in options.OPTIONS:
        default = f"`{o.default}`" if o.default != "" else ""
        crd = f"`{o.crd}`" if o.crd else ""
        rows.append(f"| `--{o.name}` | {default} | {crd} | {o.help} |")
    table = "\n".join(rows)
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        assert table in f.read(), \
            "README.md's serving flag reference is stale; it should read:\n" \
            + table
