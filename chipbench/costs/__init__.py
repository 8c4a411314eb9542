"""Operations and bytes a kernel's call needs, from its shapes alone.

``chipbench/costs/<name>.py`` holds one ``cost(lanes, *, model, engine)``,
which a metric's file names under ``params.cost``. It takes one dispatch's
lanes as the harness recorded them (``[(prefix_len, new_tokens), ...]``),
``model`` (every scalar field of the served ``ModelConfig``, under the
program's field names) and ``engine`` (``harness.engine_facts``), and
returns ``(flops, bytes)`` on ONE chip for ALL layers of the served model:
what the algorithm needs, not what an implementation moves."""
