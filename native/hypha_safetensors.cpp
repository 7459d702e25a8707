// Native SafeTensors access: a read-only view for the checkpoint converter.
//
// A minimal JSON header parser for the SafeTensors tensor table and mmap'd
// zero-copy reads (SafeTensorsView in hypha_tpu/native.py). Self-contained.
//
// SafeTensors layout: 8-byte LE u64 header length, JSON header
// {"name": {"dtype": "F32", "shape": [...], "data_offsets": [s, e]}, ...},
// then the data section. Offsets are relative to the data section start.
//
// Build: g++ -O3 -march=native -shared -fPIC ... (see hypha_tpu/native.py)

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

namespace {

void set_err(char *err, int errlen, const std::string &msg) {
  if (err != nullptr && errlen > 0) {
    std::snprintf(err, static_cast<size_t>(errlen), "%s", msg.c_str());
  }
}

// ---------------------------------------------------------------------------
// Minimal JSON parser: just the SafeTensors header subset — objects, strings,
// arrays of integers, integers. No floats/bools/null/nesting beyond spec.
// ---------------------------------------------------------------------------

struct TensorInfo {
  std::string name;
  std::string dtype;
  std::vector<int64_t> shape;
  int64_t begin = 0;
  int64_t end = 0;
};

struct Parser {
  const char *p;
  const char *limit;
  std::string error;

  bool fail(const std::string &msg) {
    if (error.empty()) error = msg;
    return false;
  }
  void ws() {
    while (p < limit && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r')) ++p;
  }
  bool expect(char c) {
    ws();
    if (p >= limit || *p != c) return fail(std::string("expected '") + c + "'");
    ++p;
    return true;
  }
  bool peek(char c) {
    ws();
    return p < limit && *p == c;
  }
  bool string(std::string *out) {
    ws();
    if (p >= limit || *p != '"') return fail("expected string");
    ++p;
    out->clear();
    while (p < limit && *p != '"') {
      if (*p == '\\') {
        ++p;
        if (p >= limit) return fail("bad escape");
        switch (*p) {
          case '"': out->push_back('"'); break;
          case '\\': out->push_back('\\'); break;
          case '/': out->push_back('/'); break;
          case 'n': out->push_back('\n'); break;
          case 't': out->push_back('\t'); break;
          case 'r': out->push_back('\r'); break;
          case 'b': out->push_back('\b'); break;
          case 'f': out->push_back('\f'); break;
          case 'u': {  // \uXXXX: keep ASCII, reject surrogates (names are
                       // tree paths; exotic escapes mean a hostile file)
            if (limit - p < 5) return fail("bad \\u escape");
            int v = 0;
            for (int i = 1; i <= 4; ++i) {
              char c = p[i];
              v <<= 4;
              if (c >= '0' && c <= '9') v |= c - '0';
              else if (c >= 'a' && c <= 'f') v |= c - 'a' + 10;
              else if (c >= 'A' && c <= 'F') v |= c - 'A' + 10;
              else return fail("bad \\u escape");
            }
            if (v > 0x7f) return fail("non-ascii \\u escape unsupported");
            out->push_back(static_cast<char>(v));
            p += 4;
            break;
          }
          default: return fail("unknown escape");
        }
        ++p;
      } else {
        out->push_back(*p++);
      }
    }
    if (p >= limit) return fail("unterminated string");
    ++p;  // closing quote
    return true;
  }
  bool integer(int64_t *out) {
    ws();
    bool neg = false;
    if (p < limit && *p == '-') { neg = true; ++p; }
    if (p >= limit || *p < '0' || *p > '9') return fail("expected integer");
    int64_t v = 0;
    while (p < limit && *p >= '0' && *p <= '9') {
      int digit = *p - '0';
      // Overflow is UB and a wrapped offset could pass the bounds check —
      // a hostile header must be rejected, not reinterpreted.
      if (v > (INT64_MAX - digit) / 10) return fail("integer overflow");
      v = v * 10 + digit;
      ++p;
    }
    *out = neg ? -v : v;
    return true;
  }
  bool int_array(std::vector<int64_t> *out) {
    if (!expect('[')) return false;
    out->clear();
    if (peek(']')) { ++p; return true; }
    while (true) {
      int64_t v;
      if (!integer(&v)) return false;
      out->push_back(v);
      ws();
      if (p < limit && *p == ',') { ++p; continue; }
      return expect(']');
    }
  }
  // Skip any value (for __metadata__): strings or flat objects of strings.
  bool skip_value() {
    ws();
    if (p >= limit) return fail("eof in value");
    if (*p == '"') { std::string s; return string(&s); }
    if (*p == '{') {
      ++p;
      if (peek('}')) { ++p; return true; }
      while (true) {
        std::string k, v;
        if (!string(&k) || !expect(':') || !skip_value()) return false;
        ws();
        if (p < limit && *p == ',') { ++p; continue; }
        return expect('}');
      }
    }
    if (*p == '[') { std::vector<int64_t> a; return int_array(&a); }
    int64_t i;
    return integer(&i);
  }
};

bool parse_header(const char *json, int64_t len, std::vector<TensorInfo> *out,
                  std::string *error) {
  Parser ps{json, json + len, {}};
  out->clear();
  if (!ps.expect('{')) { *error = ps.error; return false; }
  if (ps.peek('}')) return true;
  while (true) {
    TensorInfo info;
    if (!ps.string(&info.name) || !ps.expect(':')) { *error = ps.error; return false; }
    if (info.name == "__metadata__") {
      if (!ps.skip_value()) { *error = ps.error; return false; }
    } else {
      if (!ps.expect('{')) { *error = ps.error; return false; }
      while (true) {
        std::string key;
        if (!ps.string(&key) || !ps.expect(':')) { *error = ps.error; return false; }
        bool ok;
        if (key == "dtype") ok = ps.string(&info.dtype);
        else if (key == "shape") ok = ps.int_array(&info.shape);
        else if (key == "data_offsets") {
          std::vector<int64_t> offs;
          ok = ps.int_array(&offs) && offs.size() == 2;
          if (ok) { info.begin = offs[0]; info.end = offs[1]; }
        } else ok = ps.skip_value();
        if (!ok) { *error = ps.error.empty() ? "bad tensor entry" : ps.error; return false; }
        ps.ws();
        if (ps.p < ps.limit && *ps.p == ',') { ++ps.p; continue; }
        if (!ps.expect('}')) { *error = ps.error; return false; }
        break;
      }
      out->push_back(std::move(info));
    }
    ps.ws();
    if (ps.p < ps.limit && *ps.p == ',') { ++ps.p; continue; }
    if (!ps.expect('}')) { *error = ps.error; return false; }
    return true;
  }
}

// ---------------------------------------------------------------------------
// mmap'd SafeTensors file
// ---------------------------------------------------------------------------

struct StFile {
  void *map = nullptr;
  int64_t size = 0;
  const char *data = nullptr;  // data section start
  int64_t data_size = 0;
  std::vector<TensorInfo> tensors;

  bool open(const char *path, std::string *error) {
    int fd = ::open(path, O_RDONLY);
    if (fd < 0) { *error = std::string("open failed: ") + path; return false; }
    struct stat st{};
    if (fstat(fd, &st) != 0 || st.st_size < 8) {
      ::close(fd);
      *error = std::string("stat failed or too small: ") + path;
      return false;
    }
    size = st.st_size;
    map = mmap(nullptr, static_cast<size_t>(size), PROT_READ, MAP_PRIVATE, fd, 0);
    ::close(fd);
    if (map == MAP_FAILED) { map = nullptr; *error = "mmap failed"; return false; }
    uint64_t hlen;
    std::memcpy(&hlen, map, 8);  // little-endian hosts only (x86/arm64)
    // Unsigned compare: `8 + (int64_t)hlen > size` wraps negative (UB) for
    // hlen near INT64_MAX and would pass the check on a hostile header.
    if (hlen > static_cast<uint64_t>(size) - 8) { *error = "header overruns file"; return false; }
    const char *json = static_cast<const char *>(map) + 8;
    data = json + hlen;
    data_size = size - 8 - static_cast<int64_t>(hlen);
    if (!parse_header(json, static_cast<int64_t>(hlen), &tensors, error)) return false;
    for (const TensorInfo &t : tensors) {
      if (t.begin < 0 || t.end < t.begin || t.end > data_size) {
        *error = "tensor offsets out of bounds: " + t.name;
        return false;
      }
    }
    return true;
  }

  const TensorInfo *find(const std::string &name) const {
    for (const TensorInfo &t : tensors)
      if (t.name == name) return &t;
    return nullptr;
  }

  ~StFile() {
    if (map != nullptr) munmap(map, static_cast<size_t>(size));
  }
};

}  // namespace

// ---------------------------------------------------------------------------
// C API
// ---------------------------------------------------------------------------

extern "C" {

// Opaque mmap'd reader -----------------------------------------------------

void *st_open(const char *path, char *err, int errlen) {
  auto *f = new StFile();
  std::string error;
  if (!f->open(path, &error)) {
    set_err(err, errlen, error);
    delete f;
    return nullptr;
  }
  return f;
}

void st_close(void *handle) { delete static_cast<StFile *>(handle); }

int64_t st_count(void *handle) {
  return static_cast<int64_t>(static_cast<StFile *>(handle)->tensors.size());
}

const char *st_name(void *handle, int64_t i) {
  auto *f = static_cast<StFile *>(handle);
  if (i < 0 || i >= static_cast<int64_t>(f->tensors.size())) return nullptr;
  return f->tensors[static_cast<size_t>(i)].name.c_str();
}

// Returns data pointer; fills nbytes, dtype (short string), ndim and shape.
const void *st_tensor(void *handle, const char *name, int64_t *nbytes,
                      char *dtype, int dtype_len, int64_t *shape,
                      int max_dims, int *ndim) {
  auto *f = static_cast<StFile *>(handle);
  const TensorInfo *t = f->find(name);
  if (t == nullptr) return nullptr;
  *nbytes = t->end - t->begin;
  set_err(dtype, dtype_len, t->dtype);
  *ndim = static_cast<int>(t->shape.size());
  for (int d = 0; d < *ndim && d < max_dims; ++d) shape[d] = t->shape[static_cast<size_t>(d)];
  return f->data + t->begin;
}

}  // extern "C"
