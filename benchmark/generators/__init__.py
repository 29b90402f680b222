"""Data generators, one module per generator, found by the name a
configuration gives under `generator`. Each has `generate(params, seed)`
-> data, `TABLES`, `ddl(table)`, `columns(data, table)`,
`handles(table, data)` and `split(table, data)`; its schema file
(<name>.schema.json) holds the declared column widths the needed-bytes
arithmetic uses."""
