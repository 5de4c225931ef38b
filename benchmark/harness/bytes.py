"""The bytes a query has to move, from the schema and the row counts."""


def table_bytes(tables, nrows, schema):
    """Rows times the device width of every base-table column named in
    ``tables`` ({table: [column, ...]}), each read once."""
    total = 0
    for table, cols in tables.items():
        widths = schema[table]
        total += nrows[table] * sum(widths[c] for c in cols)
    return total
